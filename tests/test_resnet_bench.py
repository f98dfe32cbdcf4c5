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
