"""Data-plane bench smoke lane (``-m bench_smoke``, also tier-1).

Runs the real harness at a small size — few steps, small model, real
orbax saves — pinning the pipelined data-plane invariants long before
anyone reruns the full ``tpujob bench-data-plane`` artifact:

- a blocking save commits on the step thread; the PR-3 eager-async
  save commits behind the steps but still gathers the state there; a
  STAGED save does neither -- all three of the same state, all ending
  sidecar-verified;
- a PREFETCHED loop issues ZERO ``device_put`` calls on the step path
  (the transfers all ride the producer pool);
- a STAGED loop issues ZERO ``device_get`` calls on the step path
  beyond the bench's own loss-fence budget (the state gather rides the
  snapshot-stage thread);
- under a bursty producer the AUTOTUNED feed raises its depth where
  the static ``depth=2`` feed keeps its own, and never exceeds the
  ``depth_max`` budget.
"""

from __future__ import annotations

import json

import pytest

import tests.jaxenv  # noqa: F401  (forces CPU backend with 8 devices)

from pytorch_operator_tpu.workloads import dataplane_bench

pytestmark = pytest.mark.bench_smoke


@pytest.fixture(scope="module")
def smoke_result(tmp_path_factory):
    import os

    from pytorch_operator_tpu.obs import trace as obs_trace

    # The flight-recorder overhead pin below requires tracing OFF: an
    # env leak from an earlier test would void the zero-span invariant.
    os.environ.pop(obs_trace.ENV_VAR, None)
    obs_trace.reset_tracer()
    td = tmp_path_factory.mktemp("dataplane")
    # Small but real: 18 steps, 3 timed saves per cell, ~1.5 MB state.
    # checkpoint_every=6 keeps the save interval clear of the commit
    # time at this size, so the stall ordering measures the submit
    # protocol rather than max_pending backpressure.
    return dataplane_bench.run(
        steps=18, checkpoint_every=6, dim=128, batch=128,
        feed_steps=36,
        work_dir=str(td), log=lambda *_: None,
    )


def cell(result, ckpt, feed):
    return next(
        c for c in result["cells"] if c["ckpt"] == ckpt and c["feed"] == feed
    )


def feed_cell(result, mode):
    return next(
        c for c in result["feed_cells"] if c["feed_cell"] == mode
    )


class TestDataPlaneSmoke:
    def test_async_save_stalls_less_than_blocking(self, smoke_result):
        # THE tier-1 invariant, as the mechanism guarantees it: on the
        # same state a blocking save pays its whole commit (orbax write,
        # checksum sidecar) on the step thread, an async save pays none
        # of it there -- every commit runs behind the steps. (Which of
        # the two wall-clock stalls is smaller at smoke sizes is the
        # box's to say; the full artifact reports the ratio.)
        blocking = cell(smoke_result, "blocking", "inline")
        async_ = cell(smoke_result, "async", "inline")
        assert blocking["saves"] == async_["saves"] > 0
        assert blocking["step_thread_commits"] == blocking["saves"], blocking
        assert blocking["background_commits"] == 0, blocking
        assert async_["step_thread_commits"] == 0, async_
        assert async_["background_commits"] == async_["saves"], async_
        assert blocking["stall_ms_p50"] > 0

    def test_staged_save_stalls_less_than_async(self, smoke_result):
        """The staged pipeline's headline: a fence-only submit leaves
        the step thread nothing of the save -- no commit, and no gather
        either, where the eager-async submit still gathers every state
        leaf there, once a save at the least."""
        async_ = cell(smoke_result, "async", "inline")
        staged = cell(smoke_result, "staged", "inline")
        assert staged["step_thread_commits"] == 0, staged
        assert staged["background_commits"] == staged["saves"], staged
        assert staged["step_thread_device_gets"] == staged["device_get_budget"]
        assert async_["step_thread_gets_beyond_budget"] >= async_["saves"]

    def test_prefetched_loop_zero_inline_device_puts(self, smoke_result):
        for ckpt in ("blocking", "async", "staged"):
            pf = cell(smoke_result, ckpt, "prefetched")
            inline = cell(smoke_result, ckpt, "inline")
            # Zero transfers on the step path vs one per step inline.
            assert pf["step_thread_device_puts"] == 0, pf
            assert inline["step_thread_device_puts"] == inline["steps"]

    def test_staged_zero_step_thread_gathers_beyond_budget(self, smoke_result):
        """The staged pipeline's transfer pin: the state gather NEVER
        runs on the step thread — device_get calls there are exactly
        the bench's own loss fences. The eager-async cells show the
        contrast: one gather per state leaf per save on the step
        thread."""
        for feed in ("inline", "prefetched"):
            staged = cell(smoke_result, "staged", feed)
            assert staged["step_thread_gets_beyond_budget"] == 0, staged
            eager = cell(smoke_result, "async", feed)
            assert eager["step_thread_gets_beyond_budget"] > 0, eager
        assert (
            smoke_result["comparisons"]["staged_step_thread_gets_beyond_budget"]
            == 0
        )

    def test_every_cell_ends_sidecar_verified(self, smoke_result):
        # Async AND staged saves are first-class VERIFIED checkpoints:
        # the newest verified step equals the newest saved step in
        # every cell.
        for c in smoke_result["cells"]:
            assert c["all_saves_verified"], c
            assert c["last_verified_step"] == c["steps"]
        assert smoke_result["comparisons"]["async_saves_verified"] is True

    def test_autotuned_feed_beats_static_under_bursts(self, smoke_result):
        """The depth-autotune pin: same bursty producer, same step —
        the controller-grown buffer absorbs bursts the static depth=2
        buffer cannot, and never exceeds its budget."""
        static = feed_cell(smoke_result, "static")
        tuned = feed_cell(smoke_result, "autotuned")
        # The controller acted under the bursts, inside its budget; the
        # static buffer stayed where it was put. (Whose stall total is
        # the smaller is a wall-clock outcome of two CPU runs.)
        assert tuned["depth_peak"] > tuned["depth_initial"], tuned
        assert tuned["depth_peak"] <= tuned["depth_max"], tuned
        assert static["depth_peak"] == static["depth_initial"], static
        assert smoke_result["comparisons"]["autotuned_depth_within_max"]

    def test_tracing_disabled_adds_zero_step_path_spans(self, smoke_result):
        """The flight-recorder overhead pin (observability PR): with
        ``TPUJOB_TRACE_DIR`` unset, the fully instrumented step path
        (step spans, save spans, feed-thread spans, queue-wait spans,
        snapshot-stage spans) must emit ZERO span records —
        observability can never quietly tax the hot loop."""
        assert smoke_result["comparisons"]["trace_disabled_zero_spans"] is True
        for c in smoke_result["cells"]:
            assert c["trace_enabled"] is False, c
            assert c["span_records"] == 0, c

    def test_disabled_span_helper_cost_is_noise(self):
        """The <=1% step-time budget, pinned structurally: a disabled
        ``obs.span`` is a cached None check that hands every caller the
        one shared nullcontext -- nothing allocated, nothing recorded,
        whatever the name and arguments."""
        import contextlib

        from pytorch_operator_tpu import obs

        assert not obs.trace_enabled()
        before = obs.records_emitted()
        shared = obs.span("step", cat="step")
        assert isinstance(shared, contextlib.nullcontext)
        for step in range(1000):
            ctx = obs.span("save", cat="ckpt", step=step)
            assert ctx is shared
            with ctx:
                pass
        assert obs.records_emitted() == before

    def test_artifact_shape_is_committed_schema(self, smoke_result, tmp_path):
        out = tmp_path / "bench.json"
        dataplane_bench.run(
            steps=6, checkpoint_every=3, dim=64, batch=32,
            feed_steps=12,
            out=str(out), work_dir=str(tmp_path), log=lambda *_: None,
        )
        data = json.loads(out.read_text())
        assert data["bench"] == "data_plane"
        comp = data["comparisons"]
        for field in (
            "ckpt_stall_p50_reduction",
            "ckpt_stall_p99_reduction",
            "staged_stall_p50_reduction_vs_async",
            "staged_stall_p50_reduction_vs_blocking",
            "steps_per_sec_speedup_async",
            "steps_per_sec_speedup_staged",
            "prefetched_step_thread_puts",
            "staged_step_thread_gets_beyond_budget",
            "async_saves_verified",
            "autotune_steps_per_sec_speedup",
            "autotune_stall_reduction",
            "autotuned_depth_within_max",
        ):
            assert field in comp
        assert comp["async_saves_verified"] is True
        assert {c["feed_cell"] for c in data["feed_cells"]} == {
            "static",
            "autotuned",
        }
