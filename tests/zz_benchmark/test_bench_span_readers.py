"""The per-layer readers PR 24 adds, on what a run hands them: a recorded
context (the final ``metrics`` record and a few responses of one traced chip
run of each serving cell, ``data/ctx.*.json``) beside the small recorded
trace; and on a context with nothing to read, where each returns None."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

from benchmark import run, span_readers

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ["claim_wait_mean_ms", "slot_wait_mean_ms", "prefill_mean_ms", "slot_occupancy_pct.tpot",
       "slot_occupancy_pct.serve_tps", "decode_yield_pct.tpot", "decode_yield_pct.serve_tps",
       "host_gap_ms_per_s.ttft", "host_gap_ms_per_s.serve_tps", "attn_share_pct.train",
       "mlp_share_pct.train", "head_share_pct.tpot", "prefill_pad_pct.ttft", "prefill_pad_pct.serve_tps"]
DECLARED = [m["name"] for m in MANIFEST["per_layer"] if m["name"] in NEW]


def ctx_of(cell: str) -> dict:
    return json.loads((DATA / f"ctx.{cell}.json").read_text())


@pytest.fixture
def with_trace(tmp_path, monkeypatch):
    """The readers look for a run's trace under ``<root>/.benchrun/<cell>/trace``."""
    def place(cell):
        there = tmp_path / ".benchrun" / cell / "trace" / "plugins" / "profile" / "t"
        there.mkdir(parents=True)
        shutil.copy(DATA / "spans.xplane.pb", there / "vm.xplane.pb")
    monkeypatch.setattr(span_readers, "ROOT", tmp_path)
    return place


@pytest.mark.parametrize("name", DECLARED)
def test_nothing_to_read_is_none(name, with_trace):
    """The parent commit: no counter in the final record, no field in a
    response, no trace (and in a traced run, no name in the trace)."""
    empty = {"cell": {"name": "a-cell"}, "final": {}, "answers": [{"id": "r1", "ttft_ms": 5.0}], "reports": [{}],
             "records": [], "replicas": [], "e2e": {}}
    assert run.read_layer_metric(name, empty) is None
    there = Path(span_readers.ROOT) / ".benchrun" / "a-cell" / "trace" / "plugins" / "profile" / "t"
    there.mkdir(parents=True)
    shutil.copy(DATA / "small.xplane.pb", there / "vm.xplane.pb")  # a trace that names nothing
    assert run.read_layer_metric(name, empty) is None


def test_every_declared_reader_is_one_of_this_prs_and_has_its_cells():
    """What PR 24 declared stays declared: each metric once, on its layer, with its source, moving its suffix's
    end-to-end metric, in the cell PR 24 gave it. Later PRs append cells to a metric and metrics to the list
    (PR 28 on), so neither the workloads nor the list's end is pinned."""
    entries = {m["name"]: m for m in MANIFEST["per_layer"] if m["name"] in NEW}
    assert sorted(entries) == sorted(NEW) == sorted(DECLARED), "one of PR 24's metrics is not declared, or twice"
    for name, m in entries.items():
        stem, _, suffix = name.partition(".")
        cell = {"train": "train-mistral7b-1chip", "serve_tps": "serve-internlm2-longprompt"}.get(
            suffix, "serve-internlm2-chat")
        assert cell in m["workloads"], name
        assert m["moves"] == {"train": "train_tokens_per_s_chip", "serve_tps": "serve_tokens_per_s",
                              "tpot": "tpot_p50_ms"}.get(suffix, "ttft_mean_ms"), name
        share = stem in ("attn_share_pct", "mlp_share_pct", "head_share_pct")  # read from the device's trace
        assert m["layer"] == ("model step" if share else "serving engine"), name
        assert m["source"] == ("device_trace" if share else "program_span" if stem == "host_gap_ms_per_s"
                               else "program_counter"), name


def test_the_chat_cells_readers_on_a_recorded_context(with_trace, capsys):
    ctx = ctx_of("serve-internlm2-chat")
    with_trace(ctx["cell"]["name"])
    read = lambda name: run.read_layer_metric(name, ctx)
    parts = [read("claim_wait_mean_ms"), read("slot_wait_mean_ms"), read("prefill_mean_ms")]
    assert all(p is not None and p >= 0 for p in parts)
    ttft = sum(a["ttft_ms"] for a in ctx["answers"]) / len(ctx["answers"])
    assert sum(parts) == pytest.approx(ttft, abs=0.01)
    for a in ctx["answers"]:  # the identity holds on every answer of the chip run
        assert a["claim_wait_ms"] + a["slot_wait_ms"] + a["prefill_ms"] == pytest.approx(a["ttft_ms"], abs=0.01)
    final = ctx["final"]
    assert read("slot_occupancy_pct.tpot") == pytest.approx(
        100 * final["slot_blocks_occupied"] / (final["decode_blocks"] * final["slots"]), abs=1e-3)
    assert read("decode_yield_pct.tpot") == pytest.approx(
        100 * final["decode_tokens"] / final["decode_row_steps"], abs=1e-3)
    gap = read("host_gap_ms_per_s.ttft")
    assert gap == pytest.approx(1e3 * final["host_gap_s"] / ctx["seconds"])
    out = capsys.readouterr().out
    assert "host gap ms per s of a" in out and "two clocks:" in out and "idle gaps >= 0.5 ms:" in out
    # The parts printed are the record's own ``host_gap_<segment>_s``, and sum to the whole.
    printed = json.loads(out.split("blocks: ", 1)[1].split("; the rest", 1)[0])
    assert len(printed) >= 6 and sum(printed.values()) == pytest.approx(gap, abs=0.01)
    assert sum(final[f"host_gap_{k}_s"] for k in printed) == pytest.approx(final["host_gap_s"], abs=1e-9)
    rest = json.loads(out.split("time, ms per s: ", 1)[1].splitlines()[0])
    assert {"first_token", "decode_fence", "dispatch", "idle"} <= set(rest) and not set(rest) & set(printed)
    # The admission and prefill counters are read on the line after it.
    assert f"admissions: {final['admitted']} in {final['admit_rounds']} rounds" in out
    assert f"prefill chunks {final['prefill_chunks']} " in out
    assert read("prefill_pad_pct.ttft") == pytest.approx(
        100 * final["prefill_pad_tokens"] / (final["prefill_tokens"] + final["prefill_pad_tokens"]), abs=1e-3)
    head = read("head_share_pct.tpot")
    assert head is not None and 0 < head < 100
    # One reduction a run: the second reader found the first one's beside the trace.
    assert (Path(span_readers.ROOT) / ".benchrun" / ctx["cell"]["name"] / "span_reduce.json").is_file()


def test_the_longprompt_cells_readers_on_a_recorded_context():
    ctx = ctx_of("serve-internlm2-longprompt")
    read = lambda name: run.read_layer_metric(name, ctx)
    final = ctx["final"]
    assert read("slot_occupancy_pct.serve_tps") == final["slot_occupancy_pct"]
    assert read("decode_yield_pct.serve_tps") == final["decode_yield_pct"] and 0 < final["decode_yield_pct"] < 100
    assert read("host_gap_ms_per_s.serve_tps") == pytest.approx(1e3 * final["host_gap_s"] / ctx["seconds"])
    assert read("prefill_pad_pct.serve_tps") == final["prefill_pad_pct"] and 0 < final["prefill_pad_pct"] < 10


def test_the_scope_shares_are_parts_of_the_busy_time(with_trace):
    ctx = {"cell": {"name": "a-train-cell"}, "final": {}}
    with_trace("a-train-cell")
    attn, mlp = run.read_layer_metric("attn_share_pct.train", ctx), run.read_layer_metric("mlp_share_pct.train", ctx)
    assert 0 < attn < 100 and 0 < mlp < 100 and attn + mlp < 100
