"""Interleaved A/B harness for ResNet throughput experiments.

Throughput can drift within a session by more than a small effect is
worth, so back-to-back process-level A/B cannot resolve one. This
harness compiles every variant in ONE process and alternates timed
windows A,B,...,A,B,... — drift hits all variants equally, and the
min-over-windows estimator per variant gives a same-instant comparison.

Usage:
    python -m pytorch_operator_tpu.workloads.resnet_ab \
        --variants plain,s2d --rounds 6
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time


# name -> ResNet model kwargs overriding the benchmark defaults.
# A variant may carry a per-variant global batch: "plain@256".
VARIANTS = {
    "plain": {},
    "s2d": {"s2d_stem": True},
    "bn-bf16": {"bn_f32_stats": False},
    "s2d+bn-bf16": {"s2d_stem": True, "bn_f32_stats": False},
}


def parse_variant(spec: str):
    """'name@batch' -> (spec, model_kwargs, batch_override)."""
    name, _, b = spec.partition("@")
    if name not in VARIANTS:
        raise SystemExit(f"unknown variant {name!r}; have {list(VARIANTS)}")
    return spec, VARIANTS[name], int(b) if b else None


def run_ab(
    *,
    variant_names,
    depth: int = 50,
    batch_size: int = 128,
    image_size: int = 224,
    classes: int = 1000,
    steps: int = 30,
    rounds: int = 6,
    lr: float = 0.1,
    momentum: float = 0.9,
    log=print,
) -> dict:
    import jax
    import jax.numpy as jnp

    from ..models import resnet as resnet_lib
    from ..parallel import make_mesh
    from ..parallel.data import global_batch
    from .datasets import synthetic_images
    from .resnet_bench import build_train_state, make_train_chunk

    model_cls = resnet_lib.BY_DEPTH[depth]
    n_dev = jax.device_count()
    mesh = make_mesh({"dp": n_dev})
    parsed = [parse_variant(s) for s in variant_names]
    log(
        f"[ab] ResNet-{depth} base batch {batch_size} {image_size}px on "
        f"{jax.devices()[0].platform}; variants: {', '.join(variant_names)}"
    )

    runs = {}
    batches = {}
    for spec, kwargs, batch_override in parsed:
        batch = max((batch_override or batch_size) // n_dev, 1) * n_dev
        if batch not in batches:
            hx, hy = synthetic_images(batch, image_size, image_size, classes)
            batches[batch] = (
                global_batch(hx.astype(jnp.bfloat16), mesh),
                global_batch(hy, mesh),
            )
        gx, gy = batches[batch]
        model = model_cls(num_classes=classes, **kwargs)
        state = build_train_state(
            model, mesh, lr=lr, momentum=momentum, seed=0, image_size=image_size
        )
        params, batch_stats, opt_state, tx = state
        chunk_fn = make_train_chunk(model, tx, steps)
        t0 = time.time()
        params, batch_stats, opt_state, loss = chunk_fn(
            params, batch_stats, opt_state, gx, gy
        )
        float(jax.device_get(loss))
        log(f"[ab] {spec}: compiled+warm in {time.time() - t0:.1f}s")
        runs[spec] = {
            "state": (params, batch_stats, opt_state),
            "fn": chunk_fn,
            "batch": batch,
            "dt": math.inf,
            "loss": None,
        }

    for r in range(rounds):
        for spec in runs:
            v = runs[spec]
            gx, gy = batches[v["batch"]]
            params, batch_stats, opt_state = v["state"]
            t0 = time.time()
            params, batch_stats, opt_state, loss = v["fn"](
                params, batch_stats, opt_state, gx, gy
            )
            v["loss"] = float(jax.device_get(loss))
            dt = time.time() - t0
            v["state"] = (params, batch_stats, opt_state)
            v["dt"] = min(v["dt"], dt)
        log(
            f"[ab] round {r + 1}/{rounds}: "
            + "  ".join(
                f"{s}={runs[s]['batch'] * steps / runs[s]['dt']:.0f}"
                for s in runs
            )
        )

    base = variant_names[0]
    base_ips = runs[base]["batch"] * steps / runs[base]["dt"]
    out = {"steps_per_window": steps, "rounds": rounds}
    for spec in runs:
        v = runs[spec]
        ips = v["batch"] * steps / v["dt"]
        out[spec] = {
            "images_per_sec_per_chip": round(ips / n_dev, 1),
            "batch": v["batch"],
            "vs_first": round(ips / base_ips, 4),
            "final_loss": round(v["loss"], 4),
        }
        log(
            f"[ab] {spec}: {ips / n_dev:.1f} img/s/chip "
            f"({out[spec]['vs_first']:.3f}x vs {base}), loss {v['loss']:.4f}"
        )
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--variants", default="plain,s2d")
    p.add_argument("--depth", type=int, default=50)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--steps", type=int, default=30, help="steps per window")
    p.add_argument("--rounds", type=int, default=6)
    args = p.parse_args(argv)
    names = [n.strip() for n in args.variants.split(",") if n.strip()]
    for n in names:
        parse_variant(n)  # validate early
    from ..runtime import rendezvous

    rendezvous.initialize_from_env()  # honor JAX_PLATFORMS / world env
    out = run_ab(
        variant_names=names,
        depth=args.depth,
        batch_size=args.batch_size,
        image_size=args.image_size,
        steps=args.steps,
        rounds=args.rounds,
        log=lambda m: print(m, file=sys.stderr, flush=True),
    )
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
