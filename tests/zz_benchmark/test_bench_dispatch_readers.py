"""``benchmark/dispatch_reduce.py`` and the readers PR 39 adds: the pairing of
the device's ``decode_block`` runs with the engine's dispatch spans on made-up
events (two clocks that disagree, a run whose annotation opened before the
trace, a dispatch whose fence returned after it), the reduction of one small
trace recorded on a v5e chip from the program's own serve loop over a tiny
engine (``benchmark/tools/record_dispatch_trace.py``), in the process and as
the program the readers run, and every new reader on a context with nothing to
read, where it returns None."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import dispatch_reduce as D
from benchmark import run

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
TRACE = DATA / "dispatch.xplane.pb"   # PR 39: the serve loop's spans with their arguments, four dispatches
OLDER = DATA / "spans.xplane.pb"      # PR 24: the engine's spans as the parent commit writes them
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CLOSED = ["serve-internlm2-longprompt", "serve-mimo-v2.5-reasoning", "serve-nemotron3-nano-reasoning",
          "serve-phi4-mini-flash-reasoning"]
NEW = {"decode_step_ms.serve_tps": CLOSED, "decode_step_ms.tpot": ["serve-internlm2-chat"],
       "fence_tail_ms.serve_tps": CLOSED, "fence_tail_ms.ttft": ["serve-internlm2-chat"],
       "boundary_ms.serve_tps": CLOSED, "slot_occupancy_fed_pct.serve_tps": CLOSED,
       "host_gap_fed_ms_per_s.serve_tps": CLOSED}
MS = 1e6  # the events' clock counts nanoseconds


def made_up(dispatches: int, period=20.0, run=8.0, launch=0.5, tail=1.5, ahead=0.0, skip_runs=0, skip_spans=0,
            no_fence_on_last=False):
    """``dispatches`` boundaries of a serial loop, in ms: a step opens, its decode dispatch opens 1 ms later and
    takes 1 ms, the run starts ``launch`` after the dispatch's span opens, the fence returns ``tail`` after the
    run ends, then accept, harvest and a boundary of poll and respond. ``ahead``: the device's clock against
    the host's. The trace begins late for the device (``skip_runs``) or for the host (``skip_spans``)."""
    runs, spans = [], []
    for i in range(dispatches):
        t = i * period
        opened, queued = t + 1.0, t + 2.0
        start = opened + launch
        fenced = start + run + tail
        if i >= skip_runs:
            runs.append(((start - ahead) * MS, (start + run - ahead) * MS))
        if i < skip_spans:
            continue
        spans.append(("engine.step", t * MS, (fenced + 0.3) * MS, {}))
        spans.append(("engine.decode_dispatch", opened * MS, queued * MS, {"rows": 3, "steps": 8, "sized_by": "quantum"}))
        if not (no_fence_on_last and i == dispatches - 1):
            spans.append(("engine.decode_fence", (queued + 0.1) * MS, fenced * MS,
                          {"rows": 3, "steps": 8, "live": 300 + i, "attended": 1536}))
        spans.append(("engine.accept", fenced * MS, (fenced + 0.2) * MS, {}))
        spans.append(("engine.harvest", (fenced + 0.2) * MS, (fenced + 0.3) * MS, {}))
        spans.append(("serve.boundary", (fenced + 0.3) * MS, (t + period) * MS, {}))
        spans.append(("serve.respond", (fenced + 0.4) * MS, (fenced + 1.4) * MS, {"rid": f"r{i}"}))
        spans.append(("serve.poll", (fenced + 1.5) * MS, (fenced + 2.5) * MS, {}))
    return runs, (0.0, dispatches * period * MS), sorted(spans, key=lambda s: s[1])


@pytest.mark.parametrize("case, kw, want_pairs, want_shift_ms", [
    ("one clock", {}, 5, 0.0),
    ("the device's clock ahead by more than a launch", {"ahead": 2.0}, 5, 1.5),
    ("the device's clock behind by more than a tail", {"ahead": -3.0}, 5, -1.5),
    ("a run whose annotation opened before the trace", {"skip_spans": 1, "ahead": 2.0}, 4, 1.5),
    ("two such runs", {"skip_spans": 2}, 3, 0.0),
    ("a dispatch whose run the device's trace does not hold", {"skip_runs": 1}, 4, 0.0),
    ("a dispatch whose fence returned after the trace", {"no_fence_on_last": True}, 4, 0.0),
    ("a tiny program under clocks a whole run apart", {"run": 0.5, "ahead": 2.7}, 5, 2.2),
])
def test_each_run_is_paired_with_the_dispatch_that_queued_it(case, kw, want_pairs, want_shift_ms):
    runs, window, spans = made_up(5, **kw)
    dispatches = D.dispatches_of(spans)
    pairs, shift = D.pair(runs, dispatches)
    assert len(pairs) == want_pairs and shift / MS == pytest.approx(want_shift_ms, abs=1e-6), case
    # Every pair is a true one: the run lies inside its dispatch once the device's clock is shifted so.
    for (start, end), d in pairs:
        assert d["opened"] <= start + shift + 1 and end + shift <= d["fenced"] + 1, case  # to a nanosecond
        assert end - start == pytest.approx(kw.get("run", 8.0) * MS)
    red = D.reduce_events(runs, window, spans)
    assert red["dispatches"] == want_pairs and red["steps"] == 8 * want_pairs and red["row_steps"] == 24 * want_pairs
    assert 1e3 * red["device_s"] / red["steps"] == pytest.approx(kw.get("run", 8.0) / 8)
    # The tail is the difference of two clocks and carries what they disagree by; the round trip less the run
    # is a difference of two durations and does not.
    assert 1e3 * red["fence_tail_s"] / want_pairs == pytest.approx(1.5 + kw.get("ahead", 0.0))
    assert red["fence_tail_p50_s"] == pytest.approx(red["fence_tail_max_s"]) == pytest.approx(red["fence_tail_s"] / want_pairs)
    assert 1e3 * red["round_trip_less_run_s"] / red["dispatches_alone"] == pytest.approx(0.5 + 1.5)


def test_events_that_fit_no_one_shift_pair_nothing():
    runs, window, spans = made_up(4)
    runs[2] = (runs[2][0] + 15 * MS, runs[2][1] + 15 * MS)  # one run far outside its dispatch
    assert D.pair(runs, D.dispatches_of(spans)) == ([], None)
    assert D.reduce_events(runs, window, spans) == {} and D.reduce_events([], None, []) == {}
    assert D.table({}) == "no dispatch to pair in this trace"


def test_made_up_events_reduce_to_the_windows_counts_and_the_boundarys_parts():
    runs, window, spans = made_up(5)
    spans += [("engine.prefill_dispatch", (20 * i + 0.2) * MS, (20 * i + 0.6) * MS,
               {"start": 0, "slot": 1, "n_real": 100 + i, "head": int(i % 2 == 0)}) for i in (1, 2, 3)]
    spans += [("engine.first_token", (20 * i + 2.0) * MS, (20 * i + 2.05) * MS, {"n": 1}) for i in (2,)]
    red = D.reduce_events(runs, window, sorted(spans, key=lambda s: s[1]))
    assert red["live"] == sum(300 + i for i in range(5)) and red["attended"] == 5 * 1536 and red["rows"] == 15
    assert red["live_steps"] == sum(8 * (300 + i) + 3 * 8 * 7 // 2 for i in range(5))
    assert red["sized_by"] == {"quantum": 5} and red["clock_shift_ms"] == 0.0
    assert red["prefill"] == {"chunks": 3, "n_real": 306, "heads": 1, "first_tokens": 1}
    assert red["dispatches_alone"] == 2  # three dispatches had a chunk queued in front of them
    b = red["boundary"]
    assert b["count"] == 5 and b["parts_s"]["serve.respond"] == pytest.approx(5e-3)
    assert b["parts_s"]["serve.poll"] == pytest.approx(5e-3) and b["parts_s"]["serve.submit"] == 0.0
    assert b["self_s"] == pytest.approx(b["total_s"] - 10e-3) and b["total_s"] == pytest.approx(5 * 8.7e-3)
    # The host's side of a gap: a fence's return to the next dispatch's span (the last fence has none after it).
    assert red["host_gaps"] == 4
    assert red["host_gap_s"] == pytest.approx((3 * (0.3 + 8.7 + 0.2) + (0.3 + 8.7 + 1.0)) * 1e-3)  # to a chunk; to the dispatch
    assert red["after_fence_s"] == {"engine.accept": pytest.approx(1e-3), "engine.harvest": pytest.approx(0.5e-3)}
    text = D.table(red, chunk=128)
    assert "dispatches paired: 5 of 5 runs" in text and "prompt tokens = 20.312% pads" in text


@pytest.fixture(scope="module")
def recorded():
    return D.read_trace(str(TRACE))


def test_the_trace_recorded_from_the_serve_loop_on_the_chip(recorded):
    runs, window, spans = recorded
    assert len(runs) == 4 and window[0] <= runs[0][0] and runs[-1][1] <= window[1]
    names = {s[0] for s in spans}
    assert {D.DISPATCH, D.FENCE, D.CHUNK, D.FIRST, D.STEP, D.BOUNDARY, *D.PHASES, *D.AFTER_FENCE} <= names
    dispatches = D.dispatches_of(spans)
    pairs, shift = D.pair(runs, dispatches)
    # There the device's clock runs ahead: each run STARTS before the span of the dispatch that queued it opens.
    assert all(r[0] < d["opened"] for r, d in pairs) and 0.5 * MS < shift < 3 * MS
    assert [r for r, _ in pairs] == runs and [d for _, d in pairs] == dispatches[:4]
    red = D.reduce_events(runs, window, spans)
    assert red["dispatches"] == red["runs"] == 4 and red["steps"] == 32 and red["sized_by"] == {"quantum": 4}
    assert red["row_steps"] == sum(d["rows"] * d["steps"] for d in dispatches[:4]) == 120
    assert red["live"] == sum(d["live"] for d in dispatches[:4]) and red["attended"] >= red["live_steps"] > red["live"]
    assert 0.05 < 1e3 * red["device_s"] / red["steps"] < 0.1   # a tiny model's step, ms
    assert 0 < red["fence_tail_p50_s"] <= red["fence_tail_max_s"] and red["fence_tail_s"] > 0
    # Every boundary there admits, and the first dispatch's ``engine.step`` opened before the trace: none is "alone".
    assert red["dispatches_alone"] == 0 and red["round_trip_less_run_s"] == 0.0
    b = red["boundary"]
    assert b["count"] == 4 and b["self_s"] >= 0
    assert sum(b["parts_s"].values()) + b["self_s"] == pytest.approx(b["total_s"])
    assert b["parts_s"]["serve.respond"] > 0 and b["parts_s"]["serve.poll"] > 0
    assert red["prefill"]["heads"] <= red["prefill"]["chunks"] and red["prefill"]["n_real"] <= 8 * red["prefill"]["chunks"]
    # The boundary is nearly all of the host's side of a gap: what is left is the engine's, around it.
    assert red["boundary"]["total_s"] <= red["host_gap_s"] + b["max_s"] and red["host_gaps"] >= 3


def test_a_recorded_run_whose_annotation_predates_the_trace_is_dropped(recorded):
    runs, window, spans = recorded
    first = min(s[1] for s in spans if s[0] == D.DISPATCH)
    later = [s for s in spans if not (s[0] in (D.DISPATCH, D.FENCE, D.FIRST) and s[1] < first + 5 * MS)]
    pairs, _ = D.pair(runs, D.dispatches_of(later))
    assert [r for r, _ in pairs] == runs[1:]
    assert D.reduce_events(runs, window, later)["dispatches"] == 3


def test_a_trace_of_the_parent_commits_spans_reduces_to_nothing():
    runs, window, spans = D.read_trace(str(OLDER))
    assert runs and any(s[0] == D.FENCE for s in spans)  # the runs and the spans are there; the arguments are not
    assert D.dispatches_of(spans) == [] and D.reduce_events(runs, window, spans) == {}


def test_as_a_program_it_prints_the_reduction_and_imports_no_jax_to_be_imported(tmp_path):
    there = tmp_path / "plugins" / "profile" / "t"
    there.mkdir(parents=True)
    shutil.copy(TRACE, there / "vm.xplane.pb")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run([sys.executable, "-m", "benchmark.dispatch_reduce", str(tmp_path)], cwd=ROOT, env=env,
                          capture_output=True, text=True)
    assert done.returncode == 0, done.stderr[-800:]
    red = json.loads(done.stdout.strip().splitlines()[-1])
    assert red["dispatches"] == 4 and "dispatches paired: 4 of 4 runs" in done.stderr
    empty = subprocess.run([sys.executable, "-m", "benchmark.dispatch_reduce", str(tmp_path / "nothing")], cwd=ROOT,
                           env=env, capture_output=True, text=True)
    assert empty.returncode == 1 and json.loads(empty.stdout.strip().splitlines()[-1]) == {}
    probe = subprocess.run([sys.executable, "-c", "import sys; from benchmark import dispatch_reduce; "
                            "assert 'jax' not in sys.modules"], cwd=ROOT, capture_output=True, text=True)
    assert probe.returncode == 0, probe.stderr[-800:]


@pytest.fixture
def with_trace(tmp_path, monkeypatch):
    """The readers look for a run's trace under ``<root>/.benchrun/<cell>/trace``."""
    from benchmark import scope_reduce, span_readers

    def place(cell, trace):
        there = tmp_path / ".benchrun" / cell / "trace" / "plugins" / "profile" / "t"
        there.mkdir(parents=True)
        shutil.copy(trace, there / "vm.xplane.pb")
    for module in (D, scope_reduce, span_readers):
        monkeypatch.setattr(module, "ROOT", tmp_path)
    return place


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_to_read_is_none(name, with_trace):
    """The parent commit: no ``fed_*`` key in the final record, no trace; and in a traced run, spans that carry
    no arguments and no ``serve.boundary``."""
    empty = {"cell": {"name": "a-cell"}, "final": {"decode_blocks": 9, "host_gap_s": 0.5, "slot_occupancy_pct": 80.0},
             "seconds": 50.0, "answers": [], "reports": [{}], "records": [], "replicas": [], "e2e": {}}
    assert run.read_layer_metric(name, empty) is None
    with_trace("a-cell", OLDER)
    assert run.read_layer_metric(name, empty) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_every_new_metric_is_declared_for_its_cells_on_the_serving_engine(name):
    """What PR 39 declared stays declared, in the cells PR 39 gave it; a later PR appends cells (PR 41) and
    metrics (PR 41, PR 43), so neither the workloads nor the list's end is pinned."""
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert set(NEW[name]) <= set(entry["workloads"]) and entry["layer"] == "serving engine"
    stem, suffix = name.rsplit(".", 1)
    assert entry["moves"] == {"serve_tps": "serve_tokens_per_s", "tpot": "tpot_p50_ms", "ttft": "ttft_mean_ms"}[suffix]
    assert entry["better"] == ("higher" if "occupancy" in name else "lower")
    assert entry["source"] == {"decode_step_ms": "device_trace", "slot_occupancy_fed_pct": "program_counter"}.get(
        stem, "program_span")
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names.index(name) > names.index("prefill_cross_skip_pct.serve_tps")  # appended after PR 36's


def test_the_readers_on_the_recorded_trace_and_its_runs_final_record(with_trace, capsys):
    ctx = json.loads((DATA / "ctx.dispatch.json").read_text())
    with_trace(ctx["cell"]["name"], TRACE)
    read = lambda name: run.read_layer_metric(name, ctx)
    step, tail, boundary = read("decode_step_ms.serve_tps"), read("fence_tail_ms.serve_tps"), read("boundary_ms.serve_tps")
    assert step == read("decode_step_ms.tpot") and tail == read("fence_tail_ms.ttft")
    kept = json.loads((Path(D.ROOT) / ".benchrun" / ctx["cell"]["name"] / "dispatch_reduce.json").read_text())
    assert step == pytest.approx(1e3 * kept["device_s"] / 32) and tail == pytest.approx(1e3 * kept["fence_tail_s"] / 4)
    assert boundary == pytest.approx(1e3 * kept["boundary"]["total_s"] / 4) and 1.0 < boundary < 20.0
    final = ctx["final"]
    assert read("slot_occupancy_fed_pct.serve_tps") == final["fed_slot_occupancy_pct"]
    assert read("host_gap_fed_ms_per_s.serve_tps") == pytest.approx(1e3 * final["fed_host_gap_s"] / final["fed_s"])
    # The record at the newest arrival lies inside the whole one, by the drain.
    assert final["fed_decode_blocks"] < final["decode_blocks"] and final["fed_host_gap_s"] < final["host_gap_s"]
    out = capsys.readouterr().out
    for line in ("dispatches paired: 4 of 4 runs", "the whole record's means beside them:", "fence tails over 4 dispatches: median",
                 "serve.boundary: 4 in the traced window", "two clocks: the host's side of", "the record at the newest arrival:",
                 "prompt tokens = "):
        assert line in out, line
