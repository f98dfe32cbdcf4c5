"""MNIST-style training workload — the minimum end-to-end slice.

Reference analog: ``examples/mnist/mnist.py`` (SURVEY.md §2): a small CNN,
data-parallel across the world the operator wired up, reporting accuracy.
TPU-native redesign: instead of DDP gradient hooks over NCCL, the train step
is one jit-compiled SPMD program over a ``dp`` mesh spanning every device in
the job; XLA inserts the gradient all-reduce (psum) automatically from the
shardings. Multi-process worlds join via jax.distributed first
(runtime/rendezvous.py), so the same module serves 1-process SPMD on a TPU
chip and N-process gloo-CPU gangs in tests.

Exit code: 0 if final test accuracy >= --target-acc, else 1 (the job-level
Succeeded condition then mirrors "trained to target", like the reference's
example asserting on accuracy).
"""

from __future__ import annotations

import argparse
import sys
import time

from ..runtime import rendezvous


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=128, help="global batch size")
    p.add_argument("--lr", type=float, default=2e-3)
    p.add_argument("--target-acc", type=float, default=0.97)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--data-file",
        default=None,
        help="stream train batches from a packed array file via the native "
        "prefetch loader (see pytorch_operator_tpu.data.pack) instead of "
        "the in-memory dataset",
    )
    p.add_argument(
        "--prefetch", type=int, default=None, metavar="DEPTH",
        help="with --data-file: double-buffered device feed — keep DEPTH "
        "batches device-resident ahead of the step loop (0 = inline "
        "transfers). Default: spec.data_plane / TPUJOB_PREFETCH",
    )
    from .trainer import add_feed_tuning_args, resolve_feed_tuning

    add_feed_tuning_args(p)
    args = p.parse_args(argv)
    from .trainer import data_plane_env_defaults

    _, env_prefetch = data_plane_env_defaults()
    prefetch = args.prefetch if args.prefetch is not None else env_prefetch
    feed_tuning = resolve_feed_tuning(args)

    world = rendezvous.initialize_from_env()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ..models.mnist import DigitCNN
    from ..parallel import make_mesh, replicated
    from ..parallel.data import epoch_batches, global_batch
    from .datasets import digits

    t0 = time.time()
    mesh = make_mesh({"dp": jax.device_count()})
    print(
        f"[mnist] rank {world.process_id}/{world.num_processes}: "
        f"{jax.device_count()} devices, mesh dp={mesh.shape['dp']}",
        flush=True,
    )

    x_train, y_train = digits("train")
    x_test, y_test = digits("test")
    # Global batch must divide the dp extent evenly and fit the dataset
    # (a batch larger than the training set would yield zero steps/epoch).
    # With --data-file the packed file's record count is the binding cap,
    # not the in-memory set (which then only serves evaluation).
    dp = mesh.shape["dp"]
    n_train = len(x_train)
    if args.data_file:
        from ..data import read_meta

        n_train = read_meta(args.data_file).n_records
    batch = (min(args.batch_size, n_train) // dp) * dp
    if batch == 0:
        print(
            f"[mnist] error: training set ({n_train} records) smaller than "
            f"the dp extent ({dp}); cannot form a global batch",
            flush=True,
        )
        return 1

    model = DigitCNN(dtype=jnp.bfloat16)
    tx = optax.adam(args.lr)

    # ONE jitted init for params + optimizer state: eager flax init would
    # dispatch dozens of tiny ops, each its own compile, whose cache keys
    # were unstable run to run, defeating the persistent compile cache. A
    # single fused init compiles once and caches stably.
    @jax.jit
    def make_state(key):
        params = model.init(key, jnp.zeros((1, 8, 8, 1)))
        return params, tx.init(params)

    params, opt_state = make_state(jax.random.key(args.seed))

    # Replicated params/opt-state, dp-sharded batch: XLA derives the
    # gradient psum from the shardings (DDP-allreduce analog).
    rep = replicated(mesh)
    params = jax.device_put(params, rep)
    opt_state = jax.device_put(opt_state, rep)

    def loss_fn(params, bx, by):
        logits = model.apply(params, bx)
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, by)
        return loss.mean()

    @jax.jit
    def train_step(params, opt_state, bx, by):
        loss, grads = jax.value_and_grad(loss_fn)(params, bx, by)
        updates, opt_state = tx.update(grads, opt_state)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    @jax.jit
    def eval_step(params, bx, by, mask):
        logits = model.apply(params, bx)
        return jnp.sum((jnp.argmax(logits, -1) == by) * mask)

    # Train-batch source: in-memory shuffle, or the native prefetch loader
    # streaming from a packed array file (the gather then overlaps device
    # compute on a background C++ thread). epoch_iter yields DEVICE
    # global batches either way, so the step loop below is feed-agnostic.
    def put_xy(x, y):
        return global_batch(x, mesh), global_batch(y, mesh)

    loader = None
    if args.data_file:
        from ..data import open_training_loader

        loader = open_training_loader(
            args.data_file, batch, seed=args.seed,
            processes=world.num_processes,
        )
        if loader.batches_per_epoch == 0:
            print(
                f"[mnist] error: {args.data_file} holds fewer records than "
                f"the global batch ({batch}); zero steps per epoch",
                flush=True,
            )
            loader.close()
            return 1
        if prefetch > 0:
            # Double-buffered device feed: the slot copy AND the
            # host→device transfer ride the feed thread; the step loop
            # pops ready device arrays (data/device_prefetch.py).
            from ..data import prefetch_to_device

            loader = prefetch_to_device(
                loader, depth=prefetch,
                put=lambda f: put_xy(f["x"], f["y"]),
                depth_max=feed_tuning["prefetch_depth_max"] or None,
                workers=max(feed_tuning["prefetch_workers"], 1),
                autotune=feed_tuning["autotune"],
            )

            def epoch_iter(epoch):
                for _ in range(loader.batches_per_epoch):
                    _, _, dev = loader.next_batch()
                    yield dev

        else:

            def epoch_iter(epoch):
                for _ in range(loader.batches_per_epoch):
                    _, _, fields = loader.next_batch()
                    yield put_xy(fields["x"], fields["y"])

    else:

        def epoch_iter(epoch):
            for bx, by in epoch_batches(
                x_train, y_train, batch, seed=args.seed + epoch
            ):
                yield put_xy(bx, by)

    from .. import obs
    from .trainer import ProgressHeartbeat, heartbeat_reporter

    step = 0
    loss = None
    # Live telemetry heartbeat (the shared throttle, so cadence/rate
    # semantics match throughput_loop's workloads). None standalone:
    # no listener, no telemetry fences. The reporter adds the
    # flight-recorder extras (interval step time; feed stall when the
    # prefetcher is on) to each record.
    hb = ProgressHeartbeat(
        heartbeat_reporter(
            rendezvous.report_progress,
            batch=batch, n_dev=dp, unit="images/sec/chip",
            feed=loader,
        )
        if rendezvous.progress_enabled()
        else None
    )
    try:
        for epoch in range(args.epochs):
            for gx, gy in epoch_iter(epoch):
                with obs.span("step", cat="step", step=step):
                    params, opt_state, loss = train_step(
                        params, opt_state, gx, gy
                    )
                if step == 0:
                    float(jax.device_get(loss))  # real fence (not block_until_ready)
                    rendezvous.report_first_step(step)
                    print(
                        f"[mnist] first step done at +{time.time() - t0:.2f}s",
                        flush=True,
                    )
                    # The clock started before data load + compile; a
                    # rate over that window would read as a stall.
                    hb.reset(1)
                step += 1
                hb.tick(step, lambda: float(jax.device_get(loss)))
            if loss is not None:
                rendezvous.report_metrics(step, epoch=epoch, loss=float(loss))
    finally:
        if loader is not None:
            loader.close()

    # Evaluate the whole test set as ONE padded global batch: hundreds of
    # tiny eval dispatches would be pure per-dispatch overhead.
    n_eval = len(x_test)
    pad = (-n_eval) % dp
    xp = np.concatenate([x_test, np.zeros((pad,) + x_test.shape[1:], x_test.dtype)])
    yp = np.concatenate([y_test, np.zeros((pad,), y_test.dtype)])
    mask = np.concatenate([np.ones(n_eval, np.float32), np.zeros(pad, np.float32)])
    correct = int(
        eval_step(
            params,
            global_batch(xp, mesh),
            global_batch(yp, mesh),
            global_batch(mask, mesh),
        )
    )
    acc = correct / n_eval
    rendezvous.report_metrics(step, test_accuracy=acc)
    print(
        f"[mnist] rank {world.process_id}: steps={step} "
        f"test_accuracy={acc:.4f} (target {args.target_acc})",
        flush=True,
    )
    return 0 if acc >= args.target_acc else 1


if __name__ == "__main__":
    sys.exit(main())
