"""A closed loop's supply of requests (PR 43): the rule every closed traffic
file holds (``supply`` at least twice what a window sends today, a multiple of
the table's length), that a longer supply begins with the shorter one's
requests, the failure of a run whose supply does run out (its own message, its
own exception, never "no accelerator"), and the per-layer metric that shows how
near a run came. The rules on the files hold for the real tree and for a copy
with cells appended (``rules.py``). The load generator runs here against a made-up spool: a
thread that answers each request file at once. No JAX, no replica."""

from __future__ import annotations

import copy
import functools
import json
import os
import threading
import time
from pathlib import Path

import pytest

from benchmark import run
from benchmark import traffic as T
from tests.zz_benchmark.benchcells import make_copy
from tests.zz_benchmark.rules import REAL, Tree, appended, copy_cases, over  # noqa: F401

DATA = Path(__file__).resolve().parent / "data"
METRIC = "generator_supply_used_pct.serve_tps"


# ---- the rule, on the files ----


@over("mix_name", lambda tree: tree.closed)
def test_supply_is_twice_what_a_window_sends_and_whole_tables(mix_name, tree=REAL):
    mix = tree.mixes[mix_name]
    sent = mix["sent_a_window"]
    assert isinstance(sent["requests"], int) and sent["requests"] > int(mix["clients"])  # more than the first wave
    assert mix["supply"] >= 2 * sent["requests"], "no cell fails before its program is twice as fast"
    assert mix["supply"] % len(mix["lengths"]) == 0, "a longer supply must begin with this one's requests"
    assert sent["seed"] > 2**31 and "chip run" in sent["run"] and "PR " in sent["run"]  # whose run, on what
    assert "supply_note" in mix and "2 x" in mix["supply_note"]


def test_every_closed_cell_reports_the_metric_and_no_open_one(tree=REAL):
    (entry,) = [m for m in tree.manifest["per_layer"] if m["name"] == METRIC]
    assert tree.closed_cells and sorted(entry["workloads"]) == sorted(tree.closed_cells)
    sent = {w["traffic"] for w in tree.manifest["workloads"] if w["name"] in tree.closed_cells}
    assert sent == set(tree.closed), "a closed mix that no cell sends: no chip run holds its supply to the rule"
    assert (entry["layer"], entry["moves"], entry["source"], entry["better"], entry["unit"]) == (
        "load generator", "serve_tokens_per_s", "host_clock", "lower", "%")


@over("mix_name", lambda tree: tree.closed)
def test_a_longer_supply_begins_with_the_shorter_ones_requests(mix_name, tree=REAL):
    mix = tree.mixes[mix_name]
    rows = len(mix["lengths"])
    whole = T.schedule(mix, 2**31 + 4321, 50.0, 1000)
    assert len(whole) == mix["supply"]
    for supply in (rows, rows * (mix["supply"] // rows // 2)):  # one table, and half the supply's tables
        assert T.schedule({**mix, "supply": supply}, 2**31 + 4321, 50.0, 1000) == whole[:supply]


@pytest.mark.parametrize("seed", [7, 2147484941, 2**31 + 2**20 + 63])
def test_longprompts_first_512_requests_are_those_of_the_supply_of_512(seed, tree=REAL):
    """The file's history stays comparable: PR 23 to PR 42 ran it with ``supply`` 512 and PR 43 to PR 44 with
    1,024, and the same seed still gets those requests first (ids, lengths, token values), whatever row of the
    table it enters at."""
    mix = tree.mixes["longprompt-closed"]
    assert mix["supply"] > 1024 and "cycle_entry" not in mix
    now = T.schedule(mix, seed, 50.0, 92544)
    for supply in (512, 1024):
        assert now[:supply] == T.schedule({**mix, "supply": supply}, seed, 50.0, 92544)
    table = [tuple(p) for p in mix["lengths"]]
    k = seed % len(table)
    assert [(r["prompt_len"], r["max_new_tokens"]) for r in now[:3]] == (table[k:] + table[:k])[:3]
    assert [r["id"] for r in now[510:514]] == ["r00510", "r00511", "r00512", "r00513"]


# ---- the generator against a made-up spool ----


class Answering(threading.Thread):
    """The serving side of a spool, made up: every request file is answered in full at once."""

    def __init__(self, spool: Path):
        super().__init__(daemon=True)
        self.spool, self.done = spool, threading.Event()

    def run(self):
        requests, responses = self.spool / "requests", self.spool / "responses"
        responses.mkdir(parents=True)
        requests.mkdir()
        seen = set()
        while not self.done.is_set():
            for path in requests.glob("*.json"):  # renamed into place whole; the warm-up's among them
                if path.name not in seen:
                    seen.add(path.name)
                    self.answer(json.loads(path.read_text()), responses)
            time.sleep(0.002)

    @staticmethod
    def answer(body: dict, responses: Path):
        tmp = responses / f".{body['id']}.tmp"
        tmp.write_text(json.dumps({"id": body["id"], "prompt_len": len(body["prompt"]), "ttft_ms": 1.0, "tpot_ms": 1.0,
                                   "tokens": [0] * body["max_new_tokens"]}))
        os.rename(tmp, responses / f"{body['id']}.json")


class Job:
    """What ``drive_serve`` and ``stop_job`` ask of ``tpujob run``'s process; ends its made-up server."""

    def __init__(self, server: Answering | None = None):
        self.server = server

    def poll(self):
        return None if self.server is None or self.server.is_alive() else 0

    def send_signal(self, _sig):
        self.server.done.set()

    def wait(self, timeout=None):
        self.server.join(timeout)
        return 0


@pytest.fixture
def spool(tmp_path):
    server = Answering(tmp_path / "spool")
    server.start()
    yield tmp_path / "spool"
    server.done.set()
    server.join(5)


def tiny_closed(supply: int) -> dict:
    return {**json.loads((DATA / "cells" / "traffic.tiny-closed.json").read_text()), "supply": supply}


WARM = {"id": "w0", "prompt_len": 3, "max_new_tokens": 2, "prompt": [1, 2, 3]}


def test_a_supply_that_runs_out_fails_with_the_files_name_and_the_counts(spool):
    mix = tiny_closed(7)
    with pytest.raises(run.SupplyRanOut) as failure:
        run.drive_serve(spool, Job(), mix, T.schedule(mix, 2**31 + 9, 5.0, 100), 5.0, dict(WARM))
    said = str(failure.value)
    assert "traffic file 'tiny-closed' holds a supply of 7 requests" in said and "window of 5 s" in said
    drawn_at = float(said.split("the last was drawn ")[1].split(" s into")[0])
    answers = int(said.split(" with ")[1].split(" answers read")[0])
    assert 0.0 <= drawn_at < 5.0 and 5 <= answers <= 7  # the last was drawn with two callers at most unanswered
    assert "the program outran the traffic file" in said and "`supply`" in said and "`benchmark` PR" in said
    assert "no accelerator" not in said and "replica" not in said
    assert isinstance(failure.value, run.BenchFailure)  # still a run without a result: exit code 1


def test_a_window_fed_to_its_end_and_the_share_of_the_supply_it_used(spool):
    mix = tiny_closed(200)
    load = run.drive_serve(spool, Job(), mix, T.schedule(mix, 2**31 + 9, 0.4, 100), 0.4, dict(WARM))
    sent = len(load["sent"])
    assert 2 <= sent < 200 and run.judge_answers(load) == {"good": [r["id"] for r in load["sent"]], "failed": []}
    assert [r["id"] for r in load["sent"]] == [f"r{i:05d}" for i in range(sent)]  # the supply, in its order
    assert run.read_layer_metric(METRIC, {"load": load, "traffic": mix}) == pytest.approx(100.0 * sent / 200)


def test_run_py_adds_no_accelerator_to_no_supply_that_ran_out(tmp_path, monkeypatch, capsys):
    """The whole of ``run.py`` around the generator, with the job made up: ``main`` exits 1, prints the
    generator's own message and no result line, and sends nobody to the replica's log."""
    bench = make_copy(tmp_path / "copy", {"tiny-long": ("tiny-serve", "tiny-closed", "serve-internlm2-longprompt",
                                                        {"served_logit_gap_max": 0.05})}, suffix="-starved")
    (bench / "traffic" / "tiny-closed.json").write_text(json.dumps(tiny_closed(5)))  # the copy's, not the benchmark's
    (tmp_path / "pytorch_operator_tpu").mkdir()
    monkeypatch.setattr(run, "ROOT", tmp_path)
    jobs = []

    def start_job(state, job, env):
        jobs.append(Job(Answering(state / "spool")))
        jobs[-1].server.start()
        return jobs[-1]

    monkeypatch.setattr(run, "start_job", start_job)
    monkeypatch.setattr(run, "run_cell", functools.partial(run.run_cell, bench=bench, platform="cpu"))
    rc = run.main(["--workload", "tiny-long-starved", "--seed", str(2**31 + 9), "--seconds", "5", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 1 and not [ln for ln in out.splitlines() if ln.startswith("{")]
    assert "benchmark: no result: closed loop: traffic file 'tiny-closed' holds a supply of 5 requests" in err
    assert "no accelerator" not in err and "----" not in err  # neither the old guess nor a log's tail
    assert jobs and not jobs[0].server.is_alive()  # the job was ended all the same

    # Any other failure of the generator is still sent to the replica's log.
    def no_spool(*_a, **_k):
        raise run.BenchFailure("the serving job's spool did not come up")

    monkeypatch.setattr(run, "drive_serve", no_spool)
    assert run.main(["--workload", "tiny-long-starved", "--seed", "5", "--seconds", "5", "--trace", "0"]) == 1
    assert "did not come up (no accelerator, or the replica failed)" in capsys.readouterr().err


# ---- the reader ----


def recorded(cell: str) -> dict:
    ctx = json.loads((DATA / f"ctx.{cell}.json").read_text())
    sent = [{"id": f"r{i:05d}"} for i in range(477)]  # what a window of the longprompt cell sends (PR 43)
    return {**ctx, "traffic": T.load(ctx["cell"]["traffic"]), "load": {"sent": sent, "lateness": [], "stalls": []}}


def test_the_reader_on_a_recorded_context_is_sent_over_supply():
    ctx = recorded("serve-internlm2-longprompt")
    supply = ctx["traffic"]["supply"]
    assert run.read_layer_metric(METRIC, ctx) == pytest.approx(100.0 * 477 / supply) and supply >= 2 * 477
    ctx["load"]["sent"] = ctx["load"]["sent"][:16]
    assert run.read_layer_metric(METRIC, ctx) == pytest.approx(100.0 * 16 / supply)  # never 0 where a window ran


@pytest.mark.parametrize("ctx", [
    pytest.param(None, id="chat-open-loop"), pytest.param({}, id="no-load"),
    pytest.param({"load": {"sent": []}, "traffic": {"loop": "open"}}, id="no-supply"),
    pytest.param({"traffic": {"loop": "closed", "supply": 64}}, id="training-or-no-generator")])
def test_nothing_to_read_is_none(ctx):
    ctx = recorded("serve-internlm2-chat") if ctx is None else ctx
    assert run.read_layer_metric(METRIC, ctx) is None


# ---- the rules on the files, on a copy with cells appended ----


@pytest.mark.parametrize("rule, item", copy_cases(globals()))
def test_the_rule_holds_on_a_copy_with_cells_appended(rule, item, appended):
    rule(*item, tree=appended)


def test_a_closed_mix_that_no_cell_sends_fails_the_rule(appended):
    """The copy's ``tiny-closed-b`` is one cell's alone: without that cell its file would lie under
    ``benchmark/traffic/`` with a supply that no chip run reads."""
    manifest = copy.deepcopy(appended.manifest)
    manifest["workloads"] = [w for w in manifest["workloads"] if w["name"] != "tiny-long-b"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "tiny-long-b" in m.get("workloads", []):
            m["workloads"].remove("tiny-long-b")
    with pytest.raises(AssertionError, match="a closed mix that no cell sends"):
        test_every_closed_cell_reports_the_metric_and_no_open_one(tree=Tree(appended.root, manifest))
