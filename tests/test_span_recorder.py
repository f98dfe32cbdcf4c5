"""The repaired span recorder (obs/trace.py): a span's exit appends to a
buffer and writes nothing; spans carry ``id`` and ``parent``; the module
mirrors spans into ``jax.profiler`` only where JAX is already loaded and
never loads it; disabled means zero records.
"""

from __future__ import annotations

import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from pytorch_operator_tpu import obs
from pytorch_operator_tpu.obs import trace as obs_trace

ROOT = Path(__file__).resolve().parents[1]


class _CountingFile:
    """The recorder's file with its writes counted."""

    def __init__(self, f):
        self.f, self.writes, self.flushes = f, 0, 0

    def write(self, data):
        self.writes += 1
        return self.f.write(data)

    def flush(self):
        self.flushes += 1
        return self.f.flush()

    def __getattr__(self, name):
        return getattr(self.f, name)


def _spans(path):
    return [e for e in obs_trace.load_span_file(path) if e["ph"] == "X"]


def test_a_spans_exit_writes_nothing_until_flush(tmp_path):
    rec = obs_trace.SpanRecorder(tmp_path, "proc", flush_every=128)
    rec._f = counting = _CountingFile(rec._f)
    for i in range(40):
        with rec.span("work", "cat", i=i):
            pass
        rec.emit("hop", "serve", time.time(), 0.001, rid=f"r{i}")
    assert rec.records == 80 and counting.writes == 0 and counting.flushes == 0
    assert _spans(rec.path) == []
    rec.flush()
    assert counting.writes == 80 and counting.flushes == 1
    got = _spans(rec.path)
    assert [e["name"] for e in got[:2]] == ["work", "hop"] and got[1]["args"] == {"rid": "r0"}
    rec.flush()  # nothing buffered: no write
    assert counting.writes == 80 and counting.flushes == 1
    rec.close()


def test_a_full_buffer_is_written_and_close_writes_the_tail(tmp_path):
    rec = obs_trace.SpanRecorder(tmp_path, "proc", flush_every=8)
    for i in range(19):
        rec.emit("s", "cat", time.time(), 0.0, i=i)
    assert len(_spans(rec.path)) == 16  # two full buffers; three records wait
    rec.close()
    assert [e["args"]["i"] for e in _spans(rec.path)] == list(range(19))
    rec.emit("late", "cat", time.time(), 0.0)  # after close: dropped, no error
    assert len(_spans(rec.path)) == 19


def test_ids_and_parents_nest_per_thread(tmp_path):
    rec = obs_trace.SpanRecorder(tmp_path, "proc")

    def other():
        with rec.span("other_thread"):
            with rec.span("other_child"):
                pass

    with rec.span("outer", "cat", rid="r1"):
        with rec.span("inner"):
            with rec.span("leaf"):
                pass
            worker = threading.Thread(target=other)
            worker.start()
            worker.join(timeout=10)
            assert not worker.is_alive()
        with rec.span("sibling"):
            pass
        rec.emit("hop", "serve", time.time(), 0.0, rid="r1")
    rec.close()
    by_name = {e["name"]: e for e in _spans(rec.path)}
    ids = [e["id"] for e in by_name.values()]
    assert len(set(ids)) == len(ids) == 7
    assert "parent" not in by_name["outer"] and by_name["outer"]["args"] == {"rid": "r1"}
    assert by_name["inner"]["parent"] == by_name["outer"]["id"]
    assert by_name["leaf"]["parent"] == by_name["inner"]["id"]
    assert by_name["sibling"]["parent"] == by_name["outer"]["id"]
    # Another thread's spans do not hang under this thread's open span.
    assert "parent" not in by_name["other_thread"]
    assert by_name["other_child"]["parent"] == by_name["other_thread"]["id"]
    assert "parent" not in by_name["hop"]  # explicit endpoints: no place on a stack
    # Self time: a span less the part its children cover (what `tpujob trace` prints).
    children = [e for e in by_name.values() if e.get("parent") == by_name["outer"]["id"]]
    assert sum(c["dur"] for c in children) <= by_name["outer"]["dur"] + 1.0
    times = obs_trace.span_self_times(by_name.values())
    assert times["outer"]["self_ms"] == pytest.approx(
        (by_name["outer"]["dur"] - sum(c["dur"] for c in children)) / 1e3, abs=2e-3)
    assert times["leaf"]["self_ms"] == pytest.approx(times["leaf"]["total_ms"])


def test_self_time_is_a_span_less_the_spans_that_name_it_parent():
    spans = [
        {"ph": "X", "name": "engine.step", "pid": 1, "id": 1, "ts": 0, "dur": 100_000.0},
        {"ph": "X", "name": "engine.admit", "pid": 1, "id": 2, "parent": 1, "ts": 0, "dur": 30_000.0},
        {"ph": "X", "name": "engine.first_token", "pid": 1, "id": 3, "parent": 2, "ts": 0, "dur": 10_000.0},
        {"ph": "X", "name": "engine.admit", "pid": 1, "id": 4, "parent": 1, "ts": 0, "dur": 20_000.0},
        {"ph": "X", "name": "engine.step", "pid": 2, "id": 1, "ts": 0, "dur": 7_000.0},  # another process, same id
        {"ph": "X", "name": "slot_wait", "pid": 1, "id": 5, "ts": 0, "dur": 5_000.0},  # a hop: no parent
        {"ph": "X", "name": "old", "pid": 3, "ts": 0, "dur": 1_000.0},  # a file from before ids
        {"ph": "M", "name": "process_name", "pid": 1},
    ]
    times = obs_trace.span_self_times(spans)
    assert times["engine.step"] == {"count": 2, "total_ms": 107.0, "self_ms": 57.0}
    assert times["engine.admit"] == {"count": 2, "total_ms": 50.0, "self_ms": 40.0}
    assert times["engine.first_token"]["self_ms"] == 10.0 and times["slot_wait"]["self_ms"] == 5.0
    assert times["old"] == {"count": 1, "total_ms": 1.0, "self_ms": 1.0} and "process_name" not in times


def test_an_exception_leaves_the_stack_clean(tmp_path):
    rec = obs_trace.SpanRecorder(tmp_path, "proc")
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("raises"):
                raise ValueError("boom")
    with rec.span("after"):
        pass
    rec.close()
    by_name = {e["name"]: e for e in _spans(rec.path)}
    assert by_name["raises"]["parent"] == by_name["outer"]["id"] and "parent" not in by_name["after"]


PROBE = """
import os, sys
from pytorch_operator_tpu import obs
with obs.span("outer", cat="probe", n=1):
    with obs.span("inner"):
        pass
obs.instant("mark")
obs.flush()
print("records", obs.records_emitted(), "jax" in sys.modules)
"""


@pytest.mark.parametrize("traced", [False, True])
def test_obs_span_never_imports_jax(tmp_path, traced):
    env = {"PATH": "/usr/bin:/bin", "PYTHONPATH": str(ROOT), "TPUJOB_TRACE_DIR": str(tmp_path) if traced else ""}
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["records", "3" if traced else "0", "False"]
    if traced:
        (path,) = obs_trace.span_files(tmp_path)
        assert [e["name"] for e in _spans(path)] == ["inner", "outer", "mark"]


def test_disabled_with_jax_loaded_still_emits_nothing(monkeypatch):
    import tests.jaxenv  # noqa: F401
    import jax  # noqa: F401

    monkeypatch.delenv(obs_trace.ENV_VAR, raising=False)
    obs_trace.reset_tracer()
    before = obs.records_emitted()
    # No recorder and no profiler session: the one shared nullcontext.
    assert obs.span("engine.step", "engine", rid="r0") is obs_trace._NULL
    with obs.span("engine.step", "engine"):
        obs.instant("mark")
    obs.flush()
    assert obs.records_emitted() == before


def test_with_a_profiler_session_a_span_is_an_annotation_and_still_no_record(tmp_path, monkeypatch):
    import tests.jaxenv  # noqa: F401
    import jax

    monkeypatch.delenv(obs_trace.ENV_VAR, raising=False)
    obs_trace.reset_tracer()
    before = obs.records_emitted()
    jax.profiler.start_trace(str(tmp_path))
    try:
        cm = obs.span("probe.span", "engine", rid="r7", n=3)
        assert isinstance(cm, jax.profiler.TraceAnnotation)
        with cm:
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    assert obs.records_emitted() == before
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    got = [dict(ev.stats) for plane in data.planes if plane.name == "/host:CPU"
           for line in plane.lines for ev in line.events if ev.name == "probe.span"]
    assert len(got) == 1 and got[0]["rid"] == "r7" and int(got[0]["n"]) == 3
