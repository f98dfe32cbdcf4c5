"""ResNet + bench + driver-entry tests on the virtual CPU mesh."""

import numpy as np
import pytest

import tests.jaxenv  # noqa: F401


class TestResNetModel:
    def test_forward_shapes_and_dtype(self):
        import jax
        import jax.numpy as jnp

        from pytorch_operator_tpu.models.resnet import ResNet

        model = ResNet(stage_sizes=[1, 1], num_filters=8, num_classes=10)
        variables = model.init(
            jax.random.key(0), jnp.zeros((1, 32, 32, 3)), train=False
        )
        logits = model.apply(
            variables, jnp.zeros((4, 32, 32, 3)), train=False
        )
        assert logits.shape == (4, 10)
        assert logits.dtype == jnp.float32  # head stays f32 for stable loss
        assert "batch_stats" in variables

    def test_bf16_bn_stats_mode_trains_finite(self):
        """The experimental bn_f32_stats=False path (bf16 BN reductions)
        must produce finite logits and stats."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from pytorch_operator_tpu.models.resnet import ResNet

        model = ResNet(
            stage_sizes=[1, 1], num_filters=8, num_classes=10, bn_f32_stats=False
        )
        x = jnp.asarray(
            np.random.default_rng(0).standard_normal((4, 32, 32, 3)),
            jnp.float32,
        )
        variables = model.init(jax.random.key(0), x, train=False)
        logits, updates = model.apply(
            variables, x, train=True, mutable=["batch_stats"]
        )
        assert bool(jnp.isfinite(logits).all())
        mean_leaf = jax.tree.leaves(updates["batch_stats"])[0]
        assert mean_leaf.dtype == jnp.bfloat16  # stats really are bf16
        assert all(
            bool(jnp.isfinite(leaf.astype(jnp.float32)).all())
            for leaf in jax.tree.leaves(updates["batch_stats"])
        )

    def test_train_step_updates_params_and_stats(self):
        import jax
        import jax.numpy as jnp
        import optax

        from pytorch_operator_tpu.models.resnet import ResNet
        from pytorch_operator_tpu.parallel import make_mesh
        from pytorch_operator_tpu.workloads.resnet_bench import (
            build_train_state,
            make_train_step,
        )

        model = ResNet(stage_sizes=[1], num_filters=8, num_classes=10, dtype=jnp.float32)
        mesh = make_mesh("dp=8")
        params, stats, opt_state, tx = build_train_state(
            model, mesh, lr=0.1, momentum=0.9, seed=0, image_size=16
        )
        step = make_train_step(model, tx)
        bx = jnp.ones((8, 16, 16, 3))
        by = jnp.zeros((8,), jnp.int32)
        p2, s2, o2, loss = step(params, stats, opt_state, bx, by)
        assert np.isfinite(float(loss))
        # params moved
        diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), params, p2)
        assert max(jax.tree.leaves(diffs)) > 0
        # BN stats moved
        sdiffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), stats, s2)
        assert max(jax.tree.leaves(sdiffs)) > 0


class TestBench:
    @pytest.mark.slow
    def test_bench_smoke_emits_schema(self, capsys):
        import bench

        result = bench.run(["--smoke", "--steps", "2", "--warmup", "1"])
        # Round-4 shape: the artifact LEADS with the flagship LM (the
        # MFU carrier); ResNet rides as the continuity sub-block.
        # No "mfu": a CPU run has no published peak to be measured against.
        assert set(result) == {
            "metric",
            "value",
            "unit",
            "config",
            "seq_len",
            "final_loss",
            "resnet",
            "schedule_to_first_step_s",
            "device",
            "failed_legs",
        }
        assert result["value"] > 0
        assert result["unit"] == "tokens/sec/chip"
        assert result["device"]["platform"] == "cpu"
        assert result["device"]["count"] >= 1
        assert result["failed_legs"] == []
        rn = result["resnet"]
        assert rn["unit"] == "images/sec/chip" and rn["value"] > 0
        assert rn["vs_baseline"] > 0
        # The latency probe runs REAL supervisor jobs even in smoke mode
        # (with a pre-warmed standby, the production daemon config);
        # both phases must come back measured, not None.
        lat = result["schedule_to_first_step_s"]
        assert lat["cold"] > 0 and lat["warm"] > 0

    @pytest.mark.slow
    def test_bench_smoke_no_latency_flag(self):
        import bench

        result = bench.run(
            ["--smoke", "--steps", "2", "--warmup", "1", "--no-latency"]
        )
        assert set(result) == {
            "metric", "value", "unit", "config", "seq_len",
            "final_loss", "resnet", "device", "failed_legs",
        }

    def test_mfu_math(self):
        import bench

        # MFU is against the published peak of the device JAX reports;
        # a device that is not in the table is an error, not a default.
        peak = bench.peak_flops("TPU v5 lite")
        assert peak == 197e12
        m = bench.mfu(98.5e12, peak)
        assert m == {"model_tflops_per_sec": 98.5, "vs_peak_pct": 50.0}
        with pytest.raises(KeyError, match="no published peak"):
            bench.peak_flops("cpu")
        # The LM formula: 6N dominates at short S.
        f = bench.lm_train_flops_per_token(1e9, 16, 1024, 64)
        assert abs(f - (6e9 + 6 * 16 * 64 * 1024)) < 1


class TestDataFileMode:
    @pytest.mark.slow
    def test_trains_from_packed_file(self, tmp_path):
        """Real-data path: distinct per-step batches from the native
        prefetch loader, scanned inside one dispatch."""
        from pytorch_operator_tpu.data.pack import main as pack_main
        from pytorch_operator_tpu.workloads.resnet_bench import run_benchmark

        out = tmp_path / "syn.bin"
        assert pack_main([
            "--out", str(out), "--dataset", "synthetic",
            "--n", "64", "--height", "32", "--width", "32", "--classes", "10",
        ]) == 0
        result = run_benchmark(
            depth=18,
            batch_size=16,
            classes=10,
            steps=4,
            warmup=2,
            data_file=str(out),
            log=lambda *_: None,
        )
        assert result["input"] == "file"
        assert np.isfinite(result["final_loss"])
        assert result["value"] > 0

    @pytest.mark.slow
    def test_labels_exceeding_classes_rejected(self, tmp_path):
        from pytorch_operator_tpu.data.pack import main as pack_main
        from pytorch_operator_tpu.workloads.resnet_bench import run_benchmark

        out = tmp_path / "syn.bin"
        pack_main([
            "--out", str(out), "--dataset", "synthetic",
            "--n", "32", "--height", "16", "--width", "16", "--classes", "10",
        ])
        with pytest.raises(ValueError, match="classes"):
            run_benchmark(
                depth=18, batch_size=16, classes=4, steps=2, warmup=1,
                data_file=str(out), log=lambda *_: None,
            )

    @pytest.mark.slow
    def test_bad_label_beyond_first_chunk_rejected(self, tmp_path):
        """ADVICE r2: the old first-chunk latch sampled only the first
        drawn batches; a bad label in a later record one-hotted to a zero
        row and silently deflated the loss. The whole-file field_range
        scan must reject it up front — before any batch is drawn."""
        import numpy as np

        from pytorch_operator_tpu.data import pack_arrays
        from pytorch_operator_tpu.workloads.resnet_bench import run_benchmark

        n = 64
        x = np.random.default_rng(0).random((n, 16, 16, 3), np.float32)
        y = np.full((n,), 3, np.int32)
        y[-1] = 10  # out of range, and outside any first-chunk sample
        out = tmp_path / "bad-tail.bin"
        pack_arrays(out, {"x": x, "y": y})
        with pytest.raises(ValueError, match="classes"):
            run_benchmark(
                depth=18, batch_size=16, classes=10, steps=2, warmup=1,
                data_file=str(out), log=lambda *_: None,
            )
        y[-1] = -1  # negative ids are just as silent in one_hot
        out2 = tmp_path / "bad-neg.bin"
        pack_arrays(out2, {"x": x, "y": y})
        with pytest.raises(ValueError, match="classes"):
            run_benchmark(
                depth=18, batch_size=16, classes=10, steps=2, warmup=1,
                data_file=str(out2), log=lambda *_: None,
            )

    def test_file_smaller_than_batch_rejected(self, tmp_path):
        from pytorch_operator_tpu.data.pack import main as pack_main
        from pytorch_operator_tpu.workloads.resnet_bench import run_benchmark

        out = tmp_path / "tiny.bin"
        pack_main([
            "--out", str(out), "--dataset", "synthetic",
            "--n", "8", "--height", "16", "--width", "16",
        ])
        with pytest.raises(ValueError, match="records < global batch"):
            run_benchmark(
                depth=18, batch_size=64, steps=2, warmup=1,
                data_file=str(out), log=lambda *_: None,
            )


class TestProfileTrace:
    @pytest.mark.slow
    def test_profile_dir_writes_trace(self, tmp_path):
        from pytorch_operator_tpu.workloads.resnet_bench import run_benchmark

        prof = tmp_path / "trace"
        run_benchmark(
            depth=18,
            batch_size=8,
            image_size=32,
            classes=10,
            steps=2,
            warmup=1,
            profile_dir=str(prof),
            log=lambda *_: None,
        )
        # jax.profiler writes <dir>/plugins/profile/<ts>/*.xplane.pb
        assert list(prof.rglob("*.xplane.pb")), "no profiler trace written"


class TestTimeline:
    def test_job_timeline_spans(self):
        from pytorch_operator_tpu.api.types import TPUJob
        from pytorch_operator_tpu.controller.supervisor import job_timeline

        job = TPUJob.from_dict({"metadata": {"name": "t"}})
        job.status.submit_time = 100.0
        job.status.start_time = 101.0
        job.status.first_step_time = 105.0
        job.status.completion_time = 110.0
        spans = dict(job_timeline(job))
        assert spans["submit -> replicas launched"] == pytest.approx(1.0)
        assert spans["launch -> first step"] == pytest.approx(4.0)
        assert spans["first step -> finished"] == pytest.approx(5.0)
        assert spans["total (submit -> finished)"] == pytest.approx(10.0)

    def test_job_timeline_partial(self):
        from pytorch_operator_tpu.api.types import TPUJob
        from pytorch_operator_tpu.controller.supervisor import job_timeline

        job = TPUJob.from_dict({"metadata": {"name": "t"}})
        assert job_timeline(job) == []
        job.status.submit_time = 1.0
        job.status.start_time = 2.0
        assert [n for n, _ in job_timeline(job)] == ["submit -> replicas launched"]


class TestGraftEntry:
    def test_entry_traces(self):
        import jax

        import __graft_entry__ as g

        fn, args = g.entry()
        out = jax.eval_shape(fn, *args)
        # Flagship LM (llama 0.3b): logits [batch, seq, vocab].
        assert out.shape == (4, 1024, 32000)

    @pytest.mark.slow
    def test_dryrun_multichip_8(self, capsys):
        import __graft_entry__ as g

        g.dryrun_multichip(8)
        out = capsys.readouterr().out
        assert "[dryrun] ok" in out and "dp=1,fsdp=2,sp=2,tp=2" in out
        assert "attn=ring" in out


class TestBenchArtifactContract:
    """Round-5 driver-artifact contract (VERDICT r4 Weak #1): the FINAL
    stdout line must be a compact JSON summary that survives the
    driver's bounded tail window. Round 4's 4.3 KB single line did not,
    and the round's headline numbers were lost to the record."""

    # Worst-case full-detail dict: every block present, floats at full
    # precision, all round-5 serving fields populated.
    FULL = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": 44983.123456789,
        "unit": "tokens/sec/chip",
        "vs_baseline": 1.1085123,
        "config": "0.3b",
        "seq_len": 4096,
        "final_loss": 5.84321098765,
        "device": {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1},
        "failed_legs": ["vit"],
        "mfu": {
            "model_tflops_per_sec": 103.4,
            "vs_peak_pct": 52.5,
        },
        "resnet": {
            "metric": "resnet50_images_per_sec_per_chip",
            "value": 2706.987654321,
            "unit": "images/sec/chip",
            "vs_baseline": 1.0149876,
            "mfu": {
                "model_tflops_per_sec": 33.3,
                "vs_peak_pct": 16.9,
            },
        },
        "llama_real_data": {
            "metric": "llama_train_real_data_tokens_per_sec_per_chip",
            "value": 56969.123,
            "unit": "tokens/sec/chip",
            "data": "repo source+docs, byte-level, 90/10 held-out split",
            "final_loss": 2.123456789,
            "eval_loss": 2.4123456789,
            "chance_loss": 5.545,
            "learned": True,
        },
        "llama_1b_scale": {
            "metric": "scale_llama_train_tokens_per_sec_per_chip",
            "value": 16256.123,
            "unit": "tokens/sec/chip",
            "config": "1b",
            "params_m": 1100.123,
            "seq_len": 4096,
            "mfu": {
                "model_tflops_per_sec": 124.0,
                "vs_peak_pct": 63.0,
            },
        },
        "moe": {
            "metric": "moe_llama_train_tokens_per_sec_per_chip",
            "value": 52642.9,
            "unit": "tokens/sec/chip",
            "n_experts": 8,
            "moe_dispatch": "sparse",
            "moe_top_k": 2,
            "params_m": 1500.1,
            "active_params_m": 500.2,
            "final_loss": 6.1234,
            "mfu": {
                "model_tflops_per_sec": 76.4,
                "vs_peak_pct": 38.8,
            },
        },
        "serving_decode": {
            "metric": "serving_decode_tokens_per_sec_per_chip",
            "value": 2141.62345,
            "unit": "tokens/sec/chip",
            "config": "1b",
            "batch": 8,
            "max_decode_len": 4096,
            "weight_mb": 1234.5,
            "quantize": "int8 weights + int8 kv",
            "fp_tokens_per_sec_per_chip": 969.1234,
            "int8_stack_speedup": 2.2098765,
            "vs_baseline": 0.9956789,
            "quality": {"fp_eval_loss": 2.41, "int8_eval_loss": 2.43},
            "ttft_ms_p50": 181.234567,
            "ttft_ms_p99": 423.456789,
            "tpot_ms_p50": 3.73456789,
            "tpot_ms_p99": 5.91234567,
        },
        "bert": {
            "metric": "bert_base_seqs_per_sec_per_chip",
            "value": 1250.123,
            "unit": "seqs/sec/chip",
            "mfu": {
                "model_tflops_per_sec": 107.0,
                "vs_peak_pct": 54.3,
            },
        },
        "vit": {
            "metric": "vit_b16_images_per_sec_per_chip",
            "value": 882.123,
            "unit": "images/sec/chip",
            "mfu": {
                "model_tflops_per_sec": 46.6,
                "vs_peak_pct": 23.6,
            },
        },
        "schedule_to_first_step_s": {
            "cold": 11.234,
            "warm": 1.297,
            "cold_phases": {
                "submit_to_launch_s": 0.123,
                "launch_to_main_s": 0.456,
                "rendezvous_s": 0.01,
                "import_jax_s": 2.1,
                "client_init_s": 3.2,
                "compile_s": 4.5,
                "first_exec_s": 0.9,
            },
            "warm_phases": {
                "submit_to_launch_s": 0.1,
                "launch_to_main_s": 0.4,
                "rendezvous_s": 0.01,
                "import_jax_s": 0.3,
                "client_init_s": 0.15,
                "compile_s": 0.3,
                "first_exec_s": 0.05,
            },
        },
    }

    def test_compact_worst_case_fits_tail_window(self):
        import json

        import bench

        line = json.dumps(bench.compact(self.FULL))
        assert len(line.encode()) <= bench.COMPACT_MAX_BYTES, len(line)
        c = json.loads(line)
        # The round-over-round trackers must survive compaction.
        assert c["value"] == pytest.approx(44983.1235)
        assert c["vs_baseline"] == pytest.approx(1.1085)
        assert c["mfu_pct"] == pytest.approx(52.5)
        assert c["resnet"]["vs_baseline"] == pytest.approx(1.015)
        assert c["serving"]["vs_baseline"] == pytest.approx(0.9957)
        assert c["serving"]["int8_stack_speedup"] == pytest.approx(2.2099)
        assert c["serving"]["ttft_ms_p50"] == pytest.approx(181.2346)
        assert c["serving"]["tpot_ms_p99"] == pytest.approx(5.9123)
        assert c["serving"]["quality"] == {
            "fp_eval_loss": 2.41, "int8_eval_loss": 2.43,
        }
        assert c["real_data"]["learned"] is True
        assert c["real_data"]["eval_loss"] == pytest.approx(2.4123)
        assert c["scale_1b"]["mfu_pct"] == pytest.approx(63.0)
        assert c["moe"]["mfu_pct"] == pytest.approx(38.8)
        assert c["schedule_to_first_step_s"] == {"cold": 11.234, "warm": 1.297}
        assert c["detail"] == "BENCH_DETAIL.json"
        assert c["device"]["device_kind"] == "TPU v5 lite"
        assert c["failed_legs"] == ["vit"]
        # Phase breakdowns are detail, not trackers — they must NOT ride.
        assert "cold_phases" not in json.dumps(c)

    def test_compact_resnet_led_fallback(self):
        """If the LM leg failed, the artifact is resnet-led; compact
        must still produce a valid tracked line."""
        import json

        import bench

        out = {
            "metric": "resnet50_images_per_sec_per_chip",
            "value": 2706.9,
            "unit": "images/sec/chip",
            "vs_baseline": 1.015,
        }
        c = bench.compact(out)
        assert c["value"] == 2706.9 and c["vs_baseline"] == 1.015
        assert len(json.dumps(c).encode()) <= bench.COMPACT_MAX_BYTES

    def test_compact_degrades_on_pathological_values(self):
        """A huge leaked string can't break the line: the CORRUPT block
        drops first (largest-first eviction), the cap holds, and every
        healthy tracker survives — even when the corruption lands in an
        early-inserted block like resnet."""
        import json

        import bench

        for victim in ("vit", "resnet"):
            out = dict(self.FULL)
            out[victim] = dict(out[victim], unit="x" * 5000)
            c = bench.compact(out)
            assert len(json.dumps(c).encode()) <= bench.COMPACT_MAX_BYTES
            assert c["value"] == pytest.approx(44983.1235)
            assert victim not in c  # the culprit was evicted...
            # ...and the healthy trackers were not.
            assert c["serving"]["vs_baseline"] == pytest.approx(0.9957)
            assert c["schedule_to_first_step_s"]["warm"] == 1.297

    @pytest.mark.slow
    def test_main_final_stdout_line_is_compact(self, tmp_path):
        """End-to-end: `python bench.py --smoke` must end stdout with a
        parseable line under the cap, and write the detail sidecar."""
        import json
        import os
        import subprocess
        import sys
        from pathlib import Path

        root = Path(__file__).resolve().parent.parent
        detail = tmp_path / "detail.json"
        env = dict(os.environ, TPUJOB_BENCH_DETAIL=str(detail))
        proc = subprocess.run(
            [
                sys.executable, str(root / "bench.py"), "--smoke",
                "--steps", "2", "--warmup", "1", "--no-latency",
            ],
            cwd=root, env=env, capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        last = proc.stdout.strip().splitlines()[-1]
        assert len(last.encode()) <= 2000  # the driver's tail window
        c = json.loads(last)
        assert c["unit"] == "tokens/sec/chip" and c["value"] > 0
        assert c["resnet"]["value"] > 0
        # The line names the device it ran on and the legs that raised.
        assert c["device"]["platform"] == "cpu" and c["failed_legs"] == []
        # The sidecar holds the full detail, including what compaction
        # dropped (final_loss, ...).
        full = json.loads(detail.read_text())
        assert full["metric"] == c["metric"]
        assert "final_loss" in full and "final_loss" not in c
