"""BERT sequence-classification fine-tune with FSDP parameter sharding.

Reference analog: the BERT-base FSDP PyTorchJob target (BASELINE.json:9 —
"ZeRO / param sharding" moved onto a TPU mesh axis). Params, Adam mu/nu and
activations shard over ``fsdp`` (plus optional ``tp``) purely via the
logical-axis annotations in models/bert.py; XLA inserts the
all-gather/reduce-scatter pairs that DDP+ZeRO would do by hand.

Data: a synthetic two-topic classification set — class c draws its tokens
from the c-th half of the vocabulary, so accuracy verifies real learning
(loss→0, acc→1) with zero input-pipeline cost. ``--bert-base`` selects the
real BERT-base shape for throughput measurement.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

from ..runtime import rendezvous


def synthetic_topic_batch(batch: int, seq_len: int, vocab: int, step: int, n_classes: int = 2):
    """Class c ⇒ tokens uniform over [c·vocab/n, (c+1)·vocab/n)."""
    import numpy as np

    rng = np.random.default_rng(step)
    labels = rng.integers(0, n_classes, size=(batch,), dtype=np.int32)
    width = vocab // n_classes
    low = labels[:, None] * width
    toks = rng.integers(0, width, size=(batch, seq_len)).astype(np.int32) + low
    return toks.astype(np.int32), labels


def run(
    *,
    bert_base: bool = False,
    mesh_spec: str | None = None,
    batch_size: int = 16,
    seq_len: int = 64,
    steps: int = 30,
    warmup: int = 2,
    lr: float = 1e-4,
    lr_warmup_steps: int = 0,
    grad_clip: float | None = None,
    num_classes: int = 2,
    prefetch: int = 0,
    prefetch_depth_max: int = 0,
    feed_autotune: bool = False,
    prefetch_workers: int = 0,
    profile_dir: str | None = None,
    log=print,
) -> dict:
    import jax
    import numpy as np
    import optax

    from ..models import bert as bert_lib
    from ..parallel import activation_rules, make_mesh, named_sharding, put_global
    from .trainer import init_sharded_train_state, throughput_loop

    cfg = bert_lib.bert_base() if bert_base else bert_lib.bert_tiny()
    model = bert_lib.BertClassifier(cfg, num_classes=num_classes)

    import os

    n_dev = jax.device_count()
    mesh = make_mesh(mesh_spec or os.environ.get("TPUJOB_MESH", "fsdp=-1"))
    batch = max(batch_size // n_dev, 1) * n_dev if batch_size % n_dev else batch_size
    log(
        f"[bert] {'base' if bert_base else 'tiny'} d_model={cfg.d_model} "
        f"layers={cfg.n_layers} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
        f"batch={batch} seq={seq_len} ({jax.devices()[0].platform})"
    )

    # Shared recipe helper (one definition with llama_train).
    from .trainer import make_optimizer

    tx = make_optimizer(
        lr,
        schedule="cosine" if lr_warmup_steps > 0 else "constant",
        warmup_steps=lr_warmup_steps,
        decay_steps=steps + max(warmup, 1),
        grad_clip=grad_clip,
        weight_decay=0.01,
    )
    t_init = time.time()
    state, _ = init_sharded_train_state(
        lambda k: model.init(k, np.zeros((1, seq_len), np.int32)), tx, mesh
    )
    n_params = sum(p.size for p in jax.tree.leaves(state["params"]))
    log(f"[bert] {n_params/1e6:.1f}M params, sharded init +{time.time()-t_init:.1f}s")

    def loss_fn(params, tokens, labels):
        with activation_rules(mesh):
            logits = model.apply({"params": params}, tokens)
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
        acc = (logits.argmax(-1) == labels).mean()
        return loss, acc

    # Donated state: in-place update, no second state copy in HBM (this
    # workload never overlaps saves with steps, so donation is safe).
    @functools.partial(jax.jit, donate_argnums=(0,))
    def train_step(state, batch_xy):
        tokens, labels = batch_xy
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], tokens, labels
        )
        updates, opt_state = tx.update(grads, state["opt_state"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        return {"params": params, "opt_state": opt_state}, (loss, acc)

    tok_sharding = named_sharding(mesh, "batch", "seq")
    lbl_sharding = named_sharding(mesh, "batch")

    def host_batch(step: int):
        return synthetic_topic_batch(
            batch, seq_len, cfg.vocab_size, step, num_classes
        )

    def put_batch(toks_labels):
        toks, labels = toks_labels
        return (
            put_global(toks, tok_sharding),
            put_global(labels, lbl_sharding),
        )

    prefetcher = None
    if prefetch > 0:
        # Double-buffered device feed: batch N+1 transfers on the feed
        # thread while step N runs (data/device_prefetch.py). Same batch
        # order as inline — the producer counts the same step sequence
        # the loop would pass.
        import itertools

        from ..data.device_prefetch import DevicePrefetcher

        _feed_steps = itertools.count(0)
        prefetcher = DevicePrefetcher(
            lambda: host_batch(next(_feed_steps)), put=put_batch,
            depth=prefetch,
            depth_max=prefetch_depth_max or None,
            workers=max(prefetch_workers, 1),
            autotune=feed_autotune,
        )

        def batches(step: int):
            return prefetcher.get()

    else:

        def batches(step: int):
            return put_batch(host_batch(step))

    try:
        with mesh:
            state, (final_loss, final_acc), steps_per_sec, end_step = _loop(
                train_step, state, batches, steps, warmup, log, profile_dir,
                seqs_per_step_per_chip=batch / n_dev,
            )
    finally:
        if prefetcher is not None:
            prefetcher.close()

    seqs_per_sec = steps_per_sec * batch
    per_chip = seqs_per_sec / n_dev
    rendezvous.report_metrics(
        end_step,
        sequences_per_sec=seqs_per_sec,
        sequences_per_sec_per_chip=per_chip,
        final_loss=float(final_loss),
        final_accuracy=float(final_acc),
    )
    log(
        f"[bert] {steps} steps: {seqs_per_sec:,.1f} seq/sec ({per_chip:,.1f}/chip), "
        f"loss {float(final_loss):.3f}, batch acc {float(final_acc):.2f}"
    )
    return {
        "metric": "bert_train_sequences_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "sequences/sec/chip",
        "model": "bert-base" if bert_base else "bert-tiny",
        "params_m": round(n_params / 1e6, 1),
        "n_layers": cfg.n_layers,
        "d_model": cfg.d_model,
        "final_loss": round(float(final_loss), 4),
        "final_accuracy": round(float(final_acc), 4),
        "devices": n_dev,
    }


def _loop(
    train_step, state, batches, steps, warmup, log, profile_dir=None,
    seqs_per_step_per_chip=None,
):
    """throughput_loop variant for (loss, acc) tuples."""
    import jax

    from .trainer import throughput_loop

    def wrapped_step(state, b):
        state, (loss, acc) = train_step(state, b)
        wrapped_step.last = (loss, acc)
        return state, loss

    state, _, steps_per_sec, end_step = throughput_loop(
        wrapped_step,
        state,
        batches,
        steps=steps,
        warmup=warmup,
        device_get=jax.device_get,
        on_first_step=lambda _loss, _s: rendezvous.report_first_step(0),
        log=lambda m: log(f"[bert] {m}"),
        profile_dir=profile_dir,
        progress=(
            None
            if seqs_per_step_per_chip is None
            or not rendezvous.progress_enabled()
            else lambda s, l, sps: rendezvous.report_progress(
                s, loss=l, steps_per_sec=sps,
                throughput=sps * seqs_per_step_per_chip,
                unit="sequences/sec/chip",
            )
        ),
    )
    loss, acc = jax.device_get(wrapped_step.last)
    return state, (loss, acc), steps_per_sec, end_step


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--bert-base", action="store_true", help="real BERT-base dims")
    p.add_argument("--mesh", default=None)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument(
        "--lr-warmup-steps", type=int, default=0,
        help="linear warmup to --lr then cosine decay (0 = constant lr)",
    )
    p.add_argument(
        "--grad-clip", type=float, default=None,
        help="clip gradients to this global norm",
    )
    p.add_argument(
        "--prefetch", type=int, default=None, metavar="DEPTH",
        help="double-buffered device feed: keep DEPTH batches device-"
        "resident ahead of the step loop (0 = inline transfers). "
        "Default: spec.data_plane / TPUJOB_PREFETCH",
    )
    p.add_argument(
        "--profile-dir", default=None,
        help="write a jax.profiler trace of the timed window here",
    )
    p.add_argument("--json", action="store_true")
    from .trainer import add_feed_tuning_args, resolve_feed_tuning

    add_feed_tuning_args(p)
    args = p.parse_args(argv)

    from .trainer import data_plane_env_defaults

    _, env_prefetch = data_plane_env_defaults()
    feed_tuning = resolve_feed_tuning(args)
    world = rendezvous.initialize_from_env()
    result = run(
        bert_base=args.bert_base,
        mesh_spec=args.mesh,
        batch_size=args.batch_size,
        seq_len=args.seq_len,
        steps=args.steps,
        warmup=args.warmup,
        lr=args.lr,
        lr_warmup_steps=args.lr_warmup_steps,
        grad_clip=args.grad_clip,
        prefetch=args.prefetch if args.prefetch is not None else env_prefetch,
        prefetch_depth_max=feed_tuning["prefetch_depth_max"],
        feed_autotune=feed_tuning["autotune"],
        prefetch_workers=feed_tuning["prefetch_workers"],
        profile_dir=args.profile_dir,
        log=lambda msg: print(
            f"[rank {world.process_id}/{world.num_processes}] {msg}"
            if world.num_processes > 1
            else msg,
            flush=True,
        ),
    )
    if args.json and world.process_id == 0:
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
