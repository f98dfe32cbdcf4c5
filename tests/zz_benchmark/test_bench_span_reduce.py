"""``benchmark/span_reduce.py``: the one map it reads from the ``.xplane.pb``
itself (an operation's scope path, which ``jax.profiler.ProfileData`` does not
show), the scope and gap arithmetic on made-up events, and the reduction of
one small trace recorded on a v5e chip from a tiny serving engine
(``benchmark/tools/record_small_trace.py``), in the process and as the program
the readers run."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import span_reduce as S
from benchmark import trace_reduce as T

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
SMALL = DATA / "small.xplane.pb"   # PR 23: two named programs, nothing of the program's names
SPANS = DATA / "spans.xplane.pb"   # PR 24: engine spans, named scopes


@pytest.mark.parametrize("trace", [SMALL, SPANS], ids=lambda p: p.name)
def test_the_paths_are_of_the_operations_profile_data_lists(trace):
    from jax.profiler import ProfileData

    paths = S.op_paths(str(trace))
    assert paths and sum(p.startswith("jit(") for p in paths.values()) > 0.9 * len(paths)
    listed = {ev.name for plane in ProfileData.from_file(str(trace)).planes if plane.name.startswith("/device:TPU")
              for line in plane.lines if line.name == "XLA Ops" for ev in line.events}
    assert listed & set(paths) and (trace == SMALL or len(listed & set(paths)) > 0.5 * len(listed))  # (a copy has no path)
    devices, spans = S.read_trace(str(trace))
    assert len(devices) == 1 and {name for name, _, _ in devices[0]} == listed
    assert all(S.PROGRAM_SPAN.match(name) for name, _, _ in spans) and bool(spans) == (trace == SPANS)


def test_the_scope_path_is_a_stat_of_the_events_metadata():
    from jax.profiler import ProfileData

    paths = S.op_paths(str(SMALL))
    name = next(n for n in paths if "convolution_tanh_fusion" in n)
    assert paths[name] == "jit(prefill_chunk)/dot_general:"
    event = next(ev for plane in ProfileData.from_file(str(SMALL)).planes if plane.name.startswith("/device:TPU")
                 for line in plane.lines if line.name == "XLA Ops" for ev in line.events if ev.name == name)
    own = dict(event.stats)
    assert "device_duration_ps" in own and "tf_op" not in own  # why this module reads the file itself
    with pytest.raises(ValueError):
        S.op_paths(__file__)  # not an xplane file


def test_scopes_containers_and_the_innermost_span():
    assert S.scopes_of("jit(train_step)/jit(main)/transpose(jvp(Llama))/layers/attn/q_proj/dot_general:") == {
        "layers", "attn"}
    assert S.scopes_of("jit(decode_block)/while/body/attn/kv_dequantize/convert_element_type") == {
        "attn", "kv_dequantize"}
    assert S.scopes_of("jit(f)/transpose(jvp(loss))/mul:") == {"loss"} and S.scopes_of("jit(f)/attnx/mul") == set()
    assert S.scopes_of("") == set()
    paths = {"%while.1 = (s32[]) while(...)": "jit(f)/layers/while", "%fusion.1 = f32[2] fusion(...)": "jit(f)/layers/attn/dot",
             "%flash_fwd.2 = f32[2] custom-call(...)": "jit(f)/layers/attn/pallas_call", "%fusion.3": "jit(f)/head/dot"}
    w, f1, k, f3 = paths
    ops = [(f3, 120, 150), (w, 0, 100), (f1, 0, 40), (k, 40, 90), ("%copy.4", 150, 160), ("%copy.4", 400, 410)]
    red = S.by_scope(ops, paths)
    # The while holds the two operations under it and is left out; a copy has no path.
    assert red["scope_s"]["attn"] == pytest.approx(90e-9) and red["scope_s"]["layers"] == pytest.approx(90e-9)
    assert red["scope_s"]["head"] == pytest.approx(30e-9) and red["kernel_s"]["flash_fwd"] == pytest.approx(50e-9)
    assert red["in_a_scope_s"] == pytest.approx(120e-9)
    spans = [("engine.step", 0, 100), ("engine.admit", 10, 60), ("engine.first_token", 40, 55), ("serve.poll", 110, 120)]
    assert S.innermost(spans, 30, 130) == {"engine.admit": 15, "engine.first_token": 15, "engine.step": 40,
                                           "outside": 20, "serve.poll": 10}
    assert S.innermost([], 0, 5) == {"outside": 5}


def test_made_up_events_reduce_to_scopes_and_gaps_by_span():
    paths = {"%fusion.1": "jit(prefill_chunk)/layers/mlp/dot", "%fusion.2": "jit(decode_block)/sample/argmax"}
    ops = [("%fusion.1", 0, 1e6), ("%fusion.2", 3e6, 4e6), ("%fusion.3", 4.2e6, 5e6)]
    spans = [("engine.step", 0.5e6, 4.5e6), ("engine.decode_fence", 1.2e6, 2.5e6)]
    red = S.reduce_trace([ops], spans, paths)
    assert red["busy_s"] == pytest.approx(2.8e-3) and red["window_s"] == pytest.approx(5e-3)
    assert red["scope_s"]["mlp"] == pytest.approx(1e-3) and red["scope_s"]["sample"] == pytest.approx(1e-3)
    assert red["scope_stat"] == "tf_op" and red["in_a_scope_s"] == pytest.approx(2e-3)
    idle = red["idle"]
    assert idle["gaps"] == 1 and idle["gap_s"] == pytest.approx(2e-3)
    assert idle["by_span_s"] == {"engine.step": pytest.approx(0.7e-3), "engine.decode_fence": pytest.approx(1.3e-3)}
    assert red["spans_in_window"] == {"engine.step": 1, "engine.decode_fence": 1}
    assert "engine.decode_fence" in S.table(red) and "2.200 ms idle" in S.table(red)
    two = S.reduce_trace([ops, ops], spans, paths)  # times are a mean over the devices, the gaps a sum
    assert two["busy_s"] == pytest.approx(red["busy_s"]) and two["idle"]["gaps"] == 2


def test_nothing_to_read_is_empty_not_an_error(tmp_path):
    assert S.reduce_trace([], [], {}) == {} and S.reduce_dir(str(tmp_path)) == {}
    assert S.table({}) == "no device trace to reduce"
    # A trace of a program that names nothing (the parent commit): numbers, no scope, every gap outside.
    red = S.reduce_trace(*S.read_trace(str(SMALL)), S.op_paths(str(SMALL)))
    assert red["scope_stat"] is None and not any(red["scope_s"].values())
    assert set(red["idle"]["by_span_s"]) == {"outside"} and red["spans_in_window"] == {}


def test_the_trace_recorded_from_the_engine_on_the_chip():
    red = S.reduce_trace(*S.read_trace(str(SPANS)), S.op_paths(str(SPANS)))
    assert 0 < red["busy_s"] < red["window_s"]
    # The same events through the same interval routines: the replica's own report of this trace agrees.
    theirs = T.reduce_planes(T.read_planes(str(SPANS)))
    assert red["busy_s"] == pytest.approx(theirs["busy_s"]) and red["window_s"] == pytest.approx(theirs["window_s"])
    assert red["scope_stat"] == "tf_op"
    scope = red["scope_s"]
    # (The argmax is fused into the head's product and the cache's dequantisation into the attention's.)
    assert all(scope[s] > 0 for s in ("attn", "mlp", "head", "embed", "kv_quantize"))
    assert scope["kv_quantize"] < scope["attn"] <= red["busy_s"]
    assert 0.5 * red["busy_s"] < red["in_a_scope_s"] <= red["busy_s"]
    assert red["spans_in_window"]["engine.decode_fence"] >= 1 and red["spans_in_window"]["engine.admit"] >= 2
    idle = red["idle"]
    assert idle["gaps"] >= 3 and idle["gap_s"] <= red["window_s"] - red["busy_s"]
    by_span = idle["by_span_s"]
    assert sum(by_span.values()) == pytest.approx(idle["gap_s"])
    assert any(name.startswith("engine.") for name in by_span)
    # The probe sleeps 2 ms outside every engine span after each step: those gaps read "outside".
    assert by_span.get("outside", 0) >= 2e-3


def test_as_a_program_it_prints_the_reduction_and_imports_no_jax_to_be_imported(tmp_path):
    there = tmp_path / "plugins" / "profile" / "t"
    there.mkdir(parents=True)
    shutil.copy(SPANS, there / "vm.xplane.pb")
    done = subprocess.run([sys.executable, "-m", "benchmark.span_reduce", str(tmp_path)], cwd=ROOT,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True)
    assert done.returncode == 0, done.stderr[-800:]
    red = json.loads(done.stdout.strip().splitlines()[-1])
    assert red["scope_s"]["attn"] > 0 and "idle gaps >= 0.5 ms" in done.stderr
    empty = subprocess.run([sys.executable, "-m", "benchmark.span_reduce", str(tmp_path / "nothing")], cwd=ROOT,
                           env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True)
    assert empty.returncode == 1 and json.loads(empty.stdout.strip().splitlines()[-1]) == {}
    # Importing the module (the harness does, for ``table`` and ``find_xplane``) loads no JAX.
    probe = subprocess.run([sys.executable, "-c", "import sys; from benchmark import span_readers; "
                            "assert 'jax' not in sys.modules"], cwd=ROOT, capture_output=True, text=True)
    assert probe.returncode == 0, probe.stderr[-800:]
