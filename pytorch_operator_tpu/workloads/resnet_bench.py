"""ResNet-50 throughput benchmark + training workload.

The north-star metric (BASELINE.json:2): images/sec/chip on ResNet-50,
measured with synthetic data to isolate compute from input pipelines.
Runs as a supervisor workload or standalone (``python -m ... --steps 30``).

The train step is the real thing — SGD+momentum, batch-norm statistic
updates, label-smoothed cross-entropy, bf16 compute — not a forward-only
proxy; dp-sharded batch over every device in the world.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time

from ..runtime import rendezvous


def build_train_state(model, mesh, *, lr: float, momentum: float, seed: int, image_size: int):
    """Init replicated params/BN-state/opt-state for the dp mesh."""
    import jax
    import jax.numpy as jnp
    import optax

    from ..parallel import replicated

    from functools import partial as _partial

    variables = jax.jit(_partial(model.init, train=False))(
        jax.random.key(seed), jnp.zeros((1, image_size, image_size, 3))
    )
    params = variables["params"]
    batch_stats = variables["batch_stats"]
    tx = optax.sgd(lr, momentum=momentum, nesterov=True)
    opt_state = tx.init(params)
    rep = replicated(mesh)
    return (
        jax.device_put(params, rep),
        jax.device_put(batch_stats, rep),
        jax.device_put(opt_state, rep),
        tx,
    )


def _train_step_fn(model, tx, label_smoothing: float = 0.1):
    """The pure (unjitted) train-step body, shared by the per-step and
    chunked runners."""
    import jax
    import optax

    def loss_fn(params, batch_stats, bx, by):
        logits, updates = model.apply(
            {"params": params, "batch_stats": batch_stats},
            bx,
            train=True,
            mutable=["batch_stats"],
        )
        labels = optax.smooth_labels(
            jax.nn.one_hot(by, logits.shape[-1]), label_smoothing
        )
        loss = optax.softmax_cross_entropy(logits, labels).mean()
        return loss, updates["batch_stats"]

    def train_step(params, batch_stats, opt_state, bx, by):
        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch_stats, bx, by
        )
        updates, opt_state = tx.update(grads, opt_state)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, loss

    return train_step


def make_train_step(model, tx, label_smoothing: float = 0.1):
    import jax

    return jax.jit(_train_step_fn(model, tx, label_smoothing))


def make_train_chunk(model, tx, chunk: int, label_smoothing: float = 0.1):
    """``chunk`` train steps fused into ONE dispatch via ``lax.fori_loop``,
    with the train state donated.

    Why: a per-step host loop pays the dispatch cost every step; one
    dispatch per chunk amortizes it, and donation lets XLA update
    params/opt-state in place instead of double-buffering the whole train
    state in HBM. (The chunk size has not been re-judged on a chip cell.)
    """
    import functools

    import jax
    import jax.numpy as jnp

    step = _train_step_fn(model, tx, label_smoothing)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_chunk(params, batch_stats, opt_state, bx, by):
        def body(_, s):
            params, batch_stats, opt_state, _loss = s
            return step(params, batch_stats, opt_state, bx, by)

        return jax.lax.fori_loop(
            0, chunk, body,
            (params, batch_stats, opt_state, jnp.zeros((), jnp.float32)),
        )

    return train_chunk


def make_train_chunk_fed(model, tx, label_smoothing: float = 0.1):
    """Like :func:`make_train_chunk`, but each fused step consumes its OWN
    batch: ``bxs``/``bys`` are stacked ``[chunk, B, ...]`` and a
    ``lax.scan`` walks them. This is the real-data path — batches come
    from the native prefetch loader, one host transfer per chunk.
    """
    import functools

    import jax

    step = _train_step_fn(model, tx, label_smoothing)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_chunk(params, batch_stats, opt_state, bxs, bys):
        def body(s, batch):
            params, batch_stats, opt_state = s
            bx, by = batch
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, bx, by
            )
            return (params, batch_stats, opt_state), loss

        (params, batch_stats, opt_state), losses = jax.lax.scan(
            body, (params, batch_stats, opt_state), (bxs, bys)
        )
        return params, batch_stats, opt_state, losses[-1]

    return train_chunk


def run_benchmark(
    *,
    depth: int = 50,
    batch_size: int = 128,
    image_size: int = 224,
    classes: int = 1000,
    steps: int = 30,
    warmup: int = 5,
    lr: float = 0.1,
    momentum: float = 0.9,
    windows: int = 1,
    data_file: str | None = None,
    prefetch: int = 0,
    prefetch_depth_max: int = 0,
    feed_autotune: bool = False,
    prefetch_workers: int = 0,
    profile_dir: str | None = None,
    bn_f32_stats: bool = True,
    s2d_stem: bool = False,
    log=print,
) -> dict:
    """The ONE benchmark harness (bench.py and the workload both use it).

    Timing fence: a host transfer (device_get) of the final loss, which
    exists only once every step dispatched before it has run.

    Two protocols, both reported (``windows`` > 1):

    - **sustained** (the headline ``value``): all windows dispatched
      back-to-back with ONE fence at the end. The device stays
      continuously fed — how production training actually runs (the host
      queues ahead) — so the number pays one fence, not one per window.
      Still a strict lower bound on device throughput: the clock starts
      at the first dispatch and stops after a real device_get of the
      final loss.
    - **min fenced window** (``min_window_...`` field): each window fenced
      and the fastest kept — the round-1 protocol, retained for
      continuity.

    All windows run real training steps on the same state.

    ``data_file``: train from a packed array file via the native prefetch
    loader (SURVEY.md §7 step 5's real-data path) — every fused step gets
    its own batch (stacked per chunk, lax.scan inside one dispatch), and
    the reported throughput INCLUDES the input pipeline. Image geometry
    comes from the file; ``classes`` stays the caller's (validated against
    the file's labels). The synthetic mode isolates compute.
    """
    import jax

    from ..models import resnet as resnet_lib
    from ..parallel import make_mesh
    from ..parallel.data import global_batch
    from .datasets import synthetic_images

    warmup = max(warmup, 1)  # the first (compile) step can never be timed
    file_meta = field_x = None
    if data_file:
        from .trainer import probe_image_file

        # ResNet params are spatial-size-independent (convs + global pool),
        # so the file's H suffices for init; batches carry the real (H, W).
        # Full validation + loader open happens in open_image_feed below.
        file_meta, field_x = probe_image_file(data_file)
        if field_x is not None:
            image_size = field_x.shape[0]
    model = resnet_lib.BY_DEPTH[depth](
        num_classes=classes, bn_f32_stats=bn_f32_stats, s2d_stem=s2d_stem
    )

    n_dev = jax.device_count()
    mesh = make_mesh({"dp": n_dev})
    batch = max(batch_size // n_dev, 1) * n_dev
    geometry = (
        "x".join(str(s) for s in field_x.shape[:2]) + "px"
        if field_x is not None
        else f"{image_size}px"
    )
    log(
        f"[resnet] ResNet-{depth} on {n_dev} device(s) "
        f"({jax.devices()[0].platform}), global batch {batch}, {geometry}"
        + (f", data file {data_file}" if data_file else " (synthetic)")
    )

    params, batch_stats, opt_state, tx = build_train_state(
        model, mesh, lr=lr, momentum=momentum, seed=0, image_size=image_size
    )
    # Fuse steps into chunked dispatches (see make_train_chunk). One chunk
    # size → one compile; timed steps round UP to a chunk multiple so a run
    # never executes fewer steps than asked for. Cap 30 keeps warmup (one
    # chunk minimum) bounded; at the bench default (steps=30) each timed
    # window is a single dispatch.
    chunk = min(30, max(steps, 1))
    steps = math.ceil(max(steps, 1) / chunk) * chunk
    warm_chunks = max(1, round(warmup / chunk))
    # Feed bf16 pixels: the model's first op casts anyway, and a bf16 batch
    # halves the per-step HBM read of the largest activation tensor.
    import jax.numpy as jnp
    import numpy as np

    loader = None
    if data_file:
        from .trainer import open_image_feed

        next_batches, loader = open_image_feed(
            data_file, batch=batch, chunk=chunk, classes=classes, mesh=mesh,
            meta=file_meta, prefetch=prefetch,
            prefetch_depth_max=prefetch_depth_max, autotune=feed_autotune,
            prefetch_workers=prefetch_workers,
        )
        train_chunk = make_train_chunk_fed(model, tx)
    else:
        train_chunk = make_train_chunk(model, tx, chunk)
        hx, hy = synthetic_images(batch, image_size, image_size, classes)
        gx, gy = global_batch(hx.astype(jnp.bfloat16), mesh), global_batch(hy, mesh)

        def next_batches():
            return gx, gy

    try:
        t_start = time.time()
        for i in range(warm_chunks):
            bx, by = next_batches()
            params, batch_stats, opt_state, loss = train_chunk(
                params, batch_stats, opt_state, bx, by
            )
            if i == 0:
                float(jax.device_get(loss))
                rendezvous.report_first_step(0)
                log(
                    f"[resnet] first chunk ({chunk} steps, compile) "
                    f"+{time.time() - t_start:.1f}s"
                )
        float(jax.device_get(loss))

        from .trainer import timed_windows, window_progress

        if profile_dir and windows > 1:
            # The trace must show exactly the run the reported number
            # comes from — one sustained window, nothing else.
            log("[resnet] --profile-dir set: timing a single window")
            windows = 1

        def run_window():
            nonlocal params, batch_stats, opt_state, loss
            for _ in range(steps // chunk):
                bx, by = next_batches()
                params, batch_stats, opt_state, loss = train_chunk(
                    params, batch_stats, opt_state, bx, by
                )
            return loss

        dt, dt_sustained, n_win = timed_windows(
            run_window,
            lambda tok: float(jax.device_get(tok)),
            windows=windows,
            profile_dir=profile_dir,
            log=lambda m: log(f"[resnet] {m}"),
            # Live meter for `tpujob describe` / /metrics: one record per
            # fenced window (+ one for the sustained aggregate).
            progress=window_progress(
                rendezvous.report_progress,
                steps=steps, batch=batch, n_dev=n_dev,
                unit="images/sec/chip",
            ),
        )
        final_loss = float(jax.device_get(loss))
    finally:
        if loader is not None:
            loader.close()

    min_window_per_chip = (
        batch * steps / dt / n_dev if dt is not None else None
    )
    sustained_steps = steps * n_win
    images_per_sec = batch * sustained_steps / dt_sustained
    per_chip = images_per_sec / n_dev
    step_ms = 1000.0 * dt_sustained / sustained_steps
    rendezvous.report_metrics(
        sustained_steps,
        images_per_sec=images_per_sec,
        images_per_sec_per_chip=per_chip,
    )
    log(
        f"[resnet] sustained {sustained_steps} steps in {dt_sustained:.2f}s: "
        f"{images_per_sec:.1f} images/sec total, {per_chip:.1f} images/sec/chip, "
        f"{step_ms:.1f} ms/step, loss={final_loss:.3f} "
        + (
            f"(min fenced window: {min_window_per_chip:.1f})"
            if min_window_per_chip is not None
            else "(fenced windows skipped: profiling)"
        )
    )
    return {
        "metric": f"resnet{depth}_train_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "images_per_sec_total": round(images_per_sec, 2),
        "step_time_ms": round(step_ms, 2),
        "min_window_images_per_sec_per_chip": (
            round(min_window_per_chip, 2)
            if min_window_per_chip is not None
            else None
        ),
        "global_batch": batch,
        "devices": n_dev,
        "final_loss": round(final_loss, 4),
        "input": "file" if data_file else "synthetic",
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=128, help="global batch")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--steps", type=int, default=30, help="timed steps")
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--depth", type=int, default=50, choices=[18, 34, 50, 101, 152])
    p.add_argument(
        "--bn-bf16-stats", action="store_true",
        help="EXPERIMENTAL: batch-norm statistics AND learnable "
        "scale/bias in bf16 (flax stores stats in param_dtype); less "
        "precise normalization and BN weight updates; default f32",
    )
    p.add_argument("--classes", type=int, default=1000)
    p.add_argument(
        "--s2d-stem", action="store_true",
        help="compute the stem as a space-to-depth 4x4 conv (exact "
        "transform of the 7x7/2 stem; same params/checkpoints)",
    )
    p.add_argument(
        "--windows", type=int, default=1,
        help="time this many windows of --steps: headline value is "
        "SUSTAINED throughput over all of them pipelined (one fence); "
        "the fastest fenced window is also reported",
    )
    p.add_argument(
        "--data-file", default=None,
        help="train from a packed array file via the native prefetch loader "
        "(real-data mode; see pytorch_operator_tpu.data.pack). Throughput "
        "then includes the input pipeline.",
    )
    p.add_argument(
        "--prefetch", type=int, default=None, metavar="DEPTH",
        help="with --data-file: double-buffered device feed — keep DEPTH "
        "stacked chunks device-resident ahead of the step loop (loader "
        "pulls, stacking copy and device_put all ride a feed thread; "
        "0 = inline). Default: spec.data_plane / TPUJOB_PREFETCH",
    )
    p.add_argument(
        "--profile-dir", default=None,
        help="write a jax.profiler trace of the timed window here",
    )
    p.add_argument("--json", action="store_true", help="print a JSON result line")
    from .trainer import add_feed_tuning_args, resolve_feed_tuning

    add_feed_tuning_args(p)
    args = p.parse_args(argv)

    from .trainer import data_plane_env_defaults

    _, env_prefetch = data_plane_env_defaults()
    feed_tuning = resolve_feed_tuning(args)
    world = rendezvous.initialize_from_env()
    result = run_benchmark(
        depth=args.depth,
        batch_size=args.batch_size,
        image_size=args.image_size,
        classes=args.classes,
        steps=args.steps,
        warmup=args.warmup,
        lr=args.lr,
        momentum=args.momentum,
        windows=args.windows,
        data_file=args.data_file,
        prefetch=args.prefetch if args.prefetch is not None else env_prefetch,
        prefetch_depth_max=feed_tuning["prefetch_depth_max"],
        feed_autotune=feed_tuning["autotune"],
        prefetch_workers=feed_tuning["prefetch_workers"],
        profile_dir=args.profile_dir,
        bn_f32_stats=not args.bn_bf16_stats,
        s2d_stem=args.s2d_stem,
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
