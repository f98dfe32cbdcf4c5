"""LM workload tests: BERT-FSDP fine-tune and Llama train, in-process on
the 8-device CPU mesh — learning actually happens, optimizer state is
really ZeRO-sharded, and checkpoint resume continues rather than restarts.
"""

import os

import pytest

import tests.jaxenv  # noqa: F401

from pytorch_operator_tpu.workloads import bert_fsdp, llama_train

# Fast-lane exclusion (-m 'not slow'): full llama workload runs (resume/accum/optimizers).
pytestmark = pytest.mark.slow


def test_bert_fsdp_learns_and_shards_opt_state():
    import jax
    import numpy as np
    import optax

    from pytorch_operator_tpu.models.bert import BertClassifier, bert_tiny
    from pytorch_operator_tpu.parallel import make_mesh
    from pytorch_operator_tpu.workloads.trainer import init_sharded_train_state

    # The ZeRO claim, asserted directly: Adam mu/nu leaves carry the fsdp
    # sharding of their params.
    mesh = make_mesh({"fsdp": 8})
    model = BertClassifier(bert_tiny(), num_classes=2)
    tx = optax.adamw(1e-4)
    state, _ = init_sharded_train_state(
        lambda k: model.init(k, np.zeros((1, 16), np.int32)), tx, mesh
    )
    mu = state["opt_state"][0].mu
    q_mu = mu["bert"]["layers"]["attn"]["q_proj"]["kernel"]
    q_p = state["params"]["bert"]["layers"]["attn"]["q_proj"]["kernel"]
    assert q_mu.sharding == q_p.sharding
    assert "fsdp" in tuple(q_mu.sharding.spec)

    result = bert_fsdp.run(
        mesh_spec="fsdp=8", batch_size=32, seq_len=32, steps=40, warmup=1,
        lr=3e-4, log=lambda *_: None,
    )
    assert result["final_accuracy"] >= 0.9, result
    assert result["final_loss"] < 0.5, result


def test_llama_train_loss_decreases():
    result = llama_train.run(
        config="tiny", mesh_spec="dp=2,fsdp=2,tp=2", batch_size=8, seq_len=32,
        steps=25, warmup=1, lr=1e-3, log=lambda *_: None,
    )
    # ln(256) ≈ 5.55 is chance level on the synthetic bigram stream.
    assert result["final_loss"] < 5.0, result


def test_donation_and_remat_policy_do_not_change_numerics():
    """State donation and the 'dots' selective-remat policy are pure
    execution-strategy knobs — the loss trajectory must be bit-identical
    to the default path (same graph, different buffer/residual plans)."""
    runs = {}
    for tag, kw in {
        "control": dict(donate=False),
        "donated": dict(donate=True),
        "dots": dict(donate=True, remat=True, remat_policy="dots"),
        "full": dict(donate=True, remat=True, remat_policy="full"),
    }.items():
        runs[tag] = llama_train.run(
            config="tiny", batch_size=4, seq_len=32, steps=8, warmup=1,
            log=lambda *_: None, **kw,
        )["final_loss"]
    assert len(set(runs.values())) == 1, runs


def test_adafactor_trains_with_factored_state():
    """--optimizer adafactor must learn AND actually carry factored
    second moments (state ~N/k floats, not AdamW's 2N) — the memory
    lever at LM scale."""
    import jax

    from pytorch_operator_tpu.parallel import make_mesh
    from pytorch_operator_tpu.workloads.trainer import (
        init_sharded_train_state,
        make_optimizer,
    )

    # Adafactor's normalized updates want a higher LR than AdamW's 3e-4.
    result = llama_train.run(
        config="tiny", batch_size=8, seq_len=32, steps=40, warmup=1,
        lr=1e-1, optimizer="adafactor", log=lambda *_: None,
    )
    assert result["final_loss"] < 5.0, result

    # State-size claim, measured: count optimizer floats for both.
    from pytorch_operator_tpu.models.llama import Llama, llama_tiny
    import numpy as np

    mesh = make_mesh("dp=-1")
    model = Llama(llama_tiny(), mesh=mesh)

    def count(opt_name):
        tx = make_optimizer(1e-3, optimizer=opt_name)
        state, _ = init_sharded_train_state(
            lambda k: model.init(k, np.zeros((1, 32), np.int32)), tx, mesh
        )
        return sum(x.size for x in jax.tree.leaves(state["opt_state"]))

    adamw, adafactor = count("adamw"), count("adafactor")
    assert adafactor < adamw / 1.5, (adamw, adafactor)


def test_grad_accum_matches_unsplit_step():
    """grad_accum=N (sequential microbatches, mean grads, one update)
    must reproduce the unsplit step's loss trajectory up to f32
    reassociation — same global batch, ~N-fold less activation memory."""
    losses = {
        n: llama_train.run(
            config="tiny", batch_size=8, seq_len=32, steps=6, warmup=1,
            grad_accum=n, log=lambda *_: None,
        )["final_loss"]
        for n in (1, 2, 4)
    }
    assert losses[2] == pytest.approx(losses[1], abs=2e-3), losses
    assert losses[4] == pytest.approx(losses[1], abs=2e-3), losses


def test_grad_accum_on_pp_mesh_refused():
    with pytest.raises(ValueError, match="grad_accum.*pp"):
        llama_train.run(
            config="tiny", mesh_spec="dp=4,pp=2", batch_size=8, seq_len=32,
            steps=2, grad_accum=2, log=lambda *_: None,
        )


def test_remat_policy_without_remat_refused():
    with pytest.raises(ValueError, match="no effect without --remat"):
        llama_train.run(
            config="tiny", batch_size=2, seq_len=16, steps=2,
            remat_policy="dots", log=lambda *_: None,
        )


def test_donate_composes_with_async_checkpoint(tmp_path, monkeypatch):
    """save(block=False) snapshots the state to host BEFORE returning
    (checkpoint/async_writer.py), so donation no longer tears in-flight
    commits: the donated run's async-saved steps must all verify."""
    from pytorch_operator_tpu.checkpoint import CheckpointManager

    monkeypatch.setenv("TPUJOB_CHECKPOINT_DIR", str(tmp_path))
    llama_train.run(
        config="tiny", batch_size=2, seq_len=16, steps=3, warmup=1,
        checkpoint_every=2, async_checkpoint=True, donate=True,
        log=lambda *_: None,
    )
    with CheckpointManager(tmp_path, create=False) as mgr:
        steps = mgr.all_steps()
        assert steps, "async run committed no checkpoints"
        # Sidecar-at-commit: the newest VERIFIED step is the newest step.
        assert mgr.latest_verified_step() == steps[-1]


def test_prefetched_feed_is_batch_for_batch_identical(tmp_path):
    """--prefetch must not change WHAT trains, only WHERE the transfer
    happens: the double-buffered feed produces the same batch sequence
    as the inline path, so two same-seed runs land the same final
    loss."""
    from pytorch_operator_tpu.workloads import llama_train

    kw = dict(
        config="tiny", mesh_spec="dp=8", batch_size=8, seq_len=32,
        steps=3, warmup=1, log=lambda *_: None,
    )
    inline = llama_train.run(**kw)
    prefetched = llama_train.run(prefetch=2, **kw)
    assert prefetched["final_loss"] == pytest.approx(
        inline["final_loss"], abs=1e-5
    )


def test_llama_trains_from_packed_text_file(tmp_path):
    """The real-data LM path: a text file packed byte-level streams
    through the prefetch loader into training, with the cosine schedule
    and gradient clipping active."""
    import numpy as np

    from pytorch_operator_tpu.data import pack_arrays
    from pytorch_operator_tpu.workloads import llama_train

    # Learnable corpus: shifted arithmetic sequences (next = cur + 1
    # mod 256), so a few steps drive the loss well below chance.
    tokens = (
        (np.arange(96)[None, :] + np.arange(64)[:, None]) % 256
    ).astype(np.int32)
    f = tmp_path / "toks.bin"
    pack_arrays(f, {"tokens": tokens})

    result = llama_train.run(
        config="tiny",
        mesh_spec="dp=8",
        batch_size=8,
        seq_len=64,  # records hold 96 — sliced
        steps=20,
        warmup=1,
        lr=3e-3,
        data_file=str(f),
        lr_schedule="cosine",
        lr_warmup_steps=2,
        grad_clip=1.0,
        log=lambda *_: None,
    )
    assert np.isfinite(result["final_loss"])
    assert result["final_loss"] < 5.0  # well below chance (ln 256 ≈ 5.55)


def test_llama_eval_file_reports_heldout_loss(tmp_path):
    """--eval-file computes held-out loss + perplexity with the training
    objective, no updates; on a learnable corpus the trained model's eval
    loss lands below chance."""
    import numpy as np

    from pytorch_operator_tpu.data import pack_arrays

    tokens = (
        (np.arange(48)[None, :] + np.arange(64)[:, None]) % 256
    ).astype(np.int32)
    train_f, eval_f = tmp_path / "train.bin", tmp_path / "eval.bin"
    pack_arrays(train_f, {"tokens": tokens})
    pack_arrays(eval_f, {"tokens": (tokens + 1) % 256})

    result = llama_train.run(
        config="tiny", mesh_spec="dp=8", batch_size=8, seq_len=48,
        steps=20, warmup=1, lr=3e-3, data_file=str(train_f),
        eval_file=str(eval_f), eval_batches=2, log=lambda *_: None,
    )
    assert np.isfinite(result["eval_loss"])
    assert result["eval_loss"] < 5.55  # below ln(256) chance
    # Both fields are rounded for the JSON line — relative tolerance
    # covers the rounding at any loss magnitude.
    assert result["eval_perplexity"] == pytest.approx(
        np.exp(result["eval_loss"]), rel=2e-2
    )


def test_llama_data_file_validation(tmp_path):
    import numpy as np
    import pytest

    from pytorch_operator_tpu.data import pack_arrays
    from pytorch_operator_tpu.workloads import llama_train

    # Wrong field name.
    f1 = tmp_path / "imgs.bin"
    pack_arrays(f1, {"x": np.zeros((8, 4), np.float32)})
    with pytest.raises(ValueError, match="tokens"):
        llama_train.run(
            config="tiny", mesh_spec="dp=8", batch_size=8, seq_len=4,
            steps=1, warmup=1, data_file=str(f1), log=lambda *_: None,
        )
    # Token ids past the model vocab.
    f2 = tmp_path / "big.bin"
    pack_arrays(
        f2, {"tokens": np.full((8, 16), 9999, np.int32)}
    )
    with pytest.raises(ValueError, match="vocab"):
        llama_train.run(
            config="tiny", mesh_spec="dp=8", batch_size=8, seq_len=16,
            steps=1, warmup=1, data_file=str(f2), log=lambda *_: None,
        )
    # Negative ids clamp as silently as too-large ones — also rejected.
    f3 = tmp_path / "neg.bin"
    toks = np.zeros((8, 16), np.int32)
    toks[3, 7] = -5
    pack_arrays(f3, {"tokens": toks})
    with pytest.raises(ValueError, match="vocab"):
        llama_train.run(
            config="tiny", mesh_spec="dp=8", batch_size=8, seq_len=16,
            steps=1, warmup=1, data_file=str(f3), log=lambda *_: None,
        )


def test_llama_data_file_resume_fast_forwards(tmp_path, monkeypatch):
    """A resumed --data-file run must not replay already-consumed
    batches: the loader fast-forwards to start_step."""
    import numpy as np

    from pytorch_operator_tpu.data import pack_arrays

    monkeypatch.setenv("TPUJOB_CHECKPOINT_DIR", str(tmp_path / "ck"))
    tokens = (
        (np.arange(48)[None, :] + np.arange(64)[:, None]) % 256
    ).astype(np.int32)
    f = tmp_path / "toks.bin"
    pack_arrays(f, {"tokens": tokens})
    kw = dict(
        config="tiny", mesh_spec="dp=8", batch_size=8, seq_len=32,
        steps=4, warmup=1, checkpoint_every=3, data_file=str(f),
    )
    llama_train.run(**kw, log=lambda *_: None)
    logs = []
    llama_train.run(**kw, log=logs.append)
    assert any("resumed from checkpoint" in m for m in logs), logs
    assert any("fast-forwarded" in m for m in logs), logs


def test_llama_checkpoint_resume(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUJOB_CHECKPOINT_DIR", str(tmp_path / "ck"))
    r1 = llama_train.run(
        config="tiny", mesh_spec="fsdp=8", batch_size=8, seq_len=32,
        steps=4, warmup=1, checkpoint_every=3, log=lambda *_: None,
    )
    logs = []
    r2 = llama_train.run(
        config="tiny", mesh_spec="fsdp=8", batch_size=8, seq_len=32,
        steps=4, warmup=1, checkpoint_every=3, log=logs.append,
    )
    assert any("resumed from checkpoint" in m for m in logs), logs
    assert r2["end_step"] == r1["end_step"] + 5  # warmup(1) + steps(4)


def test_llama_async_checkpoint_resume(tmp_path, monkeypatch):
    """Async saves must still be durable by job end (mgr.close commits),
    so a follow-up run resumes exactly like the blocking path."""
    monkeypatch.setenv("TPUJOB_CHECKPOINT_DIR", str(tmp_path / "ck"))
    r1 = llama_train.run(
        config="tiny", mesh_spec="fsdp=8", batch_size=8, seq_len=32,
        steps=4, warmup=1, checkpoint_every=3, async_checkpoint=True,
        log=lambda *_: None,
    )
    logs = []
    r2 = llama_train.run(
        config="tiny", mesh_spec="fsdp=8", batch_size=8, seq_len=32,
        steps=4, warmup=1, checkpoint_every=3, async_checkpoint=True,
        log=logs.append,
    )
    assert any("resumed from checkpoint" in m for m in logs), logs
    assert r2["end_step"] == r1["end_step"] + 5


def test_llama_cosine_resume_without_horizon_warns(tmp_path, monkeypatch):
    """ADVICE r2: with --lr-schedule cosine and no --max-steps /
    --lr-decay-steps the decay horizon defaults to this LIFE's steps, so
    a resumed run (global optimizer count) trains its whole tail at
    LR~0 — detectable at resume time, so it must warn."""
    monkeypatch.setenv("TPUJOB_CHECKPOINT_DIR", str(tmp_path / "ck"))
    kw = dict(
        config="tiny", mesh_spec="fsdp=8", batch_size=8, seq_len=32,
        steps=4, warmup=1, checkpoint_every=3, lr_schedule="cosine",
    )
    logs = []
    llama_train.run(**kw, log=logs.append)
    assert not any("LR~0" in m for m in logs), logs  # fresh run: no warning
    logs = []
    llama_train.run(**kw, log=logs.append)
    assert any("resumed from checkpoint" in m for m in logs), logs
    assert any("LR~0" in m for m in logs), logs
    # An explicit global horizon silences it.
    logs = []
    llama_train.run(**kw, lr_decay_steps=64, log=logs.append)
    assert not any("LR~0" in m for m in logs), logs


def test_llama_max_steps_caps_work(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUJOB_CHECKPOINT_DIR", str(tmp_path / "ck"))
    r1 = llama_train.run(
        config="tiny", mesh_spec="fsdp=8", batch_size=8, seq_len=32,
        steps=10, warmup=1, checkpoint_every=4, max_steps=6,
        log=lambda *_: None,
    )
    assert r1["end_step"] == 6
    # resumed run respects the cap: only the remainder is run
    r2 = llama_train.run(
        config="tiny", mesh_spec="fsdp=8", batch_size=8, seq_len=32,
        steps=10, warmup=1, checkpoint_every=4, max_steps=8,
        log=lambda *_: None,
    )
    assert r2["end_step"] == 8


def test_llama_1b_plan_fits_one_v5e_chip():
    """The MFU-vs-scale config: ~1.14B params, and
    its measured on-chip recipe — bf16 params + adafactor + batch 2 —
    must fit v5e HBM with the 'dots'-remat residuals. Abstract
    (eval_shape): no compile, no arrays."""
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_operator_tpu.models import llama as llama_lib

    cfg = llama_lib.llama_1b(param_dtype=jnp.bfloat16)
    model = llama_lib.Llama(cfg)
    tx = optax.adafactor(1e-3)

    def abstract_state(key):
        params = model.init(key, np.zeros((1, 32), np.int32))["params"]
        return {"params": params, "opt_state": tx.init(params)}

    abstract = jax.eval_shape(abstract_state, jax.random.key(0))
    n_params = sum(
        math.prod(x.shape) for x in jax.tree.leaves(abstract["params"])
    )
    assert 1.0e9 < n_params < 1.3e9, f"param count {n_params/1e9:.2f}B"

    state_bytes = sum(
        math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(abstract)
    )
    # bf16 params + factored adafactor stats ~= 2.5 GiB; grads (bf16,
    # transient) + batch-2 'dots' residuals (~7 GiB measured headroom)
    # keep the whole step under v5e's 16 GiB — the measured recipe.
    assert state_bytes < 4 * 2**30, f"state {state_bytes/2**30:.1f} GiB"
