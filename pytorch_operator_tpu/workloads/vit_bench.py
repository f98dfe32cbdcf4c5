"""ViT training throughput benchmark + workload.

Companion to resnet_bench (same measurement protocols: chunked
single-dispatch steps, fenced-min + sustained windows, device_get
fence) for the transformer vision family — the architecture that
actually saturates the MXU (no batch-norm HBM reduce traffic).
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time

from ..runtime import rendezvous


def _step_fn(model, tx, label_smoothing: float = 0.1):
    import jax
    import optax

    def step(params, opt_state, bx, by):
        def loss_fn(p):
            logits = model.apply({"params": p}, bx)
            labels = optax.smooth_labels(
                jax.nn.one_hot(by, logits.shape[-1]), label_smoothing
            )
            return optax.softmax_cross_entropy(logits, labels).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


def make_train_chunk(model, tx, chunk: int, label_smoothing: float = 0.1):
    """``chunk`` AdamW train steps fused into ONE dispatch (donated state)."""
    import functools

    import jax
    import jax.numpy as jnp

    step = _step_fn(model, tx, label_smoothing)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_chunk(params, opt_state, bx, by):
        def body(_, s):
            params, opt_state, _loss = s
            return step(params, opt_state, bx, by)

        return jax.lax.fori_loop(
            0, chunk, body, (params, opt_state, jnp.zeros((), jnp.float32))
        )

    return train_chunk


def make_train_chunk_fed(model, tx, label_smoothing: float = 0.1):
    """Like :func:`make_train_chunk`, but each fused step consumes its
    OWN batch (stacked ``[chunk, B, ...]``, one host transfer per chunk)
    — the real-data path, mirroring resnet_bench's."""
    import functools

    import jax

    step = _step_fn(model, tx, label_smoothing)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_chunk(params, opt_state, bxs, bys):
        def body(s, batch):
            params, opt_state = s
            bx, by = batch
            params, opt_state, loss = step(params, opt_state, bx, by)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), (bxs, bys)
        )
        return params, opt_state, losses[-1]

    return train_chunk


def run_benchmark(
    *,
    variant: str = "b16",
    batch_size: int = 128,
    image_size: int = 224,
    classes: int = 1000,
    steps: int = 30,
    warmup: int = 5,
    lr: float = 1e-3,
    windows: int = 1,
    attn_impl: str = "dense",
    remat: bool = False,
    remat_policy: str = "full",
    data_file: str | None = None,
    prefetch: int = 0,
    prefetch_depth_max: int = 0,
    feed_autotune: bool = False,
    prefetch_workers: int = 0,
    profile_dir: str | None = None,
    log=print,
) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ..models import vit as vit_lib
    from ..parallel import make_mesh
    from ..parallel.data import global_batch
    from .datasets import synthetic_images

    if remat_policy != "full" and not remat:
        # Silently measuring the no-remat path while the user believes
        # the selective policy is active is a benchmarking trap.
        raise ValueError(
            f"--remat-policy {remat_policy} has no effect without --remat"
        )
    file_meta = None
    if data_file:
        from .trainer import probe_image_file

        # Geometry from the file; full validation (incl. the H == W
        # requirement ViT's position embeddings impose) + loader open
        # happens in open_image_feed below.
        file_meta, field_x = probe_image_file(data_file)
        if field_x is not None:
            image_size = field_x.shape[0]
    cfg = vit_lib.BY_NAME[variant](
        image_size=image_size, num_classes=classes, attn_impl=attn_impl,
        remat=remat, remat_policy=remat_policy,
    )
    model = vit_lib.ViT(cfg)
    n_dev = jax.device_count()
    mesh = make_mesh({"dp": n_dev})
    batch = max(batch_size // n_dev, 1) * n_dev
    log(
        f"[vit] ViT-{variant} d={cfg.d_model} depth={cfg.depth} on {n_dev} "
        f"device(s) ({jax.devices()[0].platform}), global batch {batch}, "
        f"{image_size}px, attn={attn_impl}"
        + (f", data file {data_file}" if data_file else " (synthetic)")
    )

    tx = optax.adamw(lr, weight_decay=0.05)

    # ONE fused init jit (params + opt state): one compile with a stable
    # cache key instead of one per eager op (the mnist cold-start lesson).
    @jax.jit
    def make_state(key):
        params = model.init(key, jnp.zeros((1, image_size, image_size, 3)))[
            "params"
        ]
        return params, tx.init(params)

    params, opt_state = jax.tree.map(
        lambda l: l.unbox() if hasattr(l, "unbox") else l,
        make_state(jax.random.key(0)),
        is_leaf=lambda l: hasattr(l, "unbox"),
    )
    n_params = sum(p.size for p in jax.tree.leaves(params))
    log(f"[vit] {n_params / 1e6:.1f}M params")

    chunk = min(30, max(steps, 1))
    steps = math.ceil(max(steps, 1) / chunk) * chunk
    warm_chunks = max(1, round(max(warmup, 1) / chunk))
    loader = None
    if data_file:
        from .trainer import open_image_feed

        next_batches, loader = open_image_feed(
            data_file, batch=batch, chunk=chunk, classes=classes, mesh=mesh,
            square=True, meta=file_meta, prefetch=prefetch,
            prefetch_depth_max=prefetch_depth_max, autotune=feed_autotune,
            prefetch_workers=prefetch_workers,
        )
        train_chunk = make_train_chunk_fed(model, tx)
    else:
        train_chunk = make_train_chunk(model, tx, chunk)
        hx, hy = synthetic_images(batch, image_size, image_size, classes)
        gx = global_batch(hx.astype(jnp.bfloat16), mesh)
        gy = global_batch(hy, mesh)

        def next_batches():
            return gx, gy

    t_start = time.time()
    try:
        for i in range(warm_chunks):
            bx, by = next_batches()
            params, opt_state, loss = train_chunk(params, opt_state, bx, by)
            if i == 0:
                float(jax.device_get(loss))
                rendezvous.report_first_step(0)
                log(f"[vit] first chunk ({chunk} steps, compile) +{time.time() - t_start:.1f}s")
        float(jax.device_get(loss))

        from .trainer import timed_windows, window_progress

        if profile_dir and windows > 1:
            log("[vit] --profile-dir set: timing a single window")
            windows = 1

        def run_window():
            nonlocal params, opt_state, loss
            for _ in range(steps // chunk):
                bx, by = next_batches()
                params, opt_state, loss = train_chunk(params, opt_state, bx, by)
            return loss

        dt, dt_sustained, n_win = timed_windows(
            run_window,
            lambda tok: float(jax.device_get(tok)),
            windows=windows,
            profile_dir=profile_dir,
            log=lambda m: log(f"[vit] {m}"),
            # Live meter for `tpujob describe` / /metrics (one record per
            # fenced window + the sustained aggregate).
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

    sustained_steps = steps * n_win
    images_per_sec = batch * sustained_steps / dt_sustained
    per_chip = images_per_sec / n_dev
    min_window = batch * steps / dt / n_dev if dt is not None else None
    rendezvous.report_metrics(
        sustained_steps,
        images_per_sec=images_per_sec,
        images_per_sec_per_chip=per_chip,
    )
    log(
        f"[vit] sustained {sustained_steps} steps in {dt_sustained:.2f}s: "
        f"{per_chip:.1f} images/sec/chip, "
        f"{1000 * dt_sustained / sustained_steps:.1f} ms/step, "
        f"loss={final_loss:.3f} "
        + (
            f"(min fenced window: {min_window:.1f})"
            if min_window is not None
            else "(fenced windows skipped: profiling)"
        )
    )
    return {
        "metric": f"vit_{variant}_train_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "min_window_images_per_sec_per_chip": (
            round(min_window, 2) if min_window is not None else None
        ),
        "params_m": round(n_params / 1e6, 1),
        "global_batch": batch,
        "devices": n_dev,
        "final_loss": round(final_loss, 4),
        "input": "file" if data_file else "synthetic",
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--variant", choices=sorted("s16 b16 l16".split()), default="b16")
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--classes", type=int, default=1000)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument(
        "--remat", action="store_true",
        help="rematerialize encoder blocks in backward (jax.checkpoint "
        "under the layer scan): ~1/3 more FLOPs for O(depth) activation "
        "memory -- unlocks larger batches",
    )
    p.add_argument(
        "--remat-policy", choices=("full", "dots"), default="full",
        help="with --remat: 'full' recomputes whole blocks in backward; "
        "'dots' saves the GEMM outputs so backward skips recomputing "
        "the MXU-bound work (more HBM)",
    )
    p.add_argument("--windows", type=int, default=1)
    p.add_argument("--attn-impl", choices=("dense", "flash"), default="dense")
    p.add_argument(
        "--data-file", default=None,
        help="train from a packed image file via the prefetch loader "
        "(pack with pytorch_operator_tpu.data.pack); image geometry "
        "comes from the file, throughput includes the input pipeline",
    )
    p.add_argument(
        "--prefetch", type=int, default=None, metavar="DEPTH",
        help="with --data-file: double-buffered device feed — keep DEPTH "
        "stacked chunks device-resident ahead of the step loop (loader "
        "pulls, stacking copy and device_put all ride a feed thread; "
        "0 = inline). Default: spec.data_plane / TPUJOB_PREFETCH",
    )
    p.add_argument("--profile-dir", default=None)
    p.add_argument("--json", action="store_true")
    from .trainer import add_feed_tuning_args, resolve_feed_tuning

    add_feed_tuning_args(p)
    args = p.parse_args(argv)

    from .trainer import data_plane_env_defaults

    _, env_prefetch = data_plane_env_defaults()
    feed_tuning = resolve_feed_tuning(args)
    world = rendezvous.initialize_from_env()
    result = run_benchmark(
        variant=args.variant,
        batch_size=args.batch_size,
        image_size=args.image_size,
        classes=args.classes,
        steps=args.steps,
        warmup=args.warmup,
        lr=args.lr,
        windows=args.windows,
        attn_impl=args.attn_impl,
        remat=args.remat,
        remat_policy=args.remat_policy,
        data_file=args.data_file,
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
