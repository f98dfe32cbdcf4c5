"""Shared training plumbing for the transformer workloads.

The key idiom: the WHOLE train state (params + optimizer state) is built
inside one jitted init whose out_shardings come from the model's logical
axis annotations — optax's tree_map over flax ``Partitioned`` params
propagates the metadata into Adam's mu/nu, so ZeRO-style sharding of the
optimizer state falls out for free (params are born sharded; nothing is
ever materialized replicated).

Reference analog: none — DDP keeps optimizer state replicated per rank and
the reference never touches it (SURVEY.md §2 parallelism table); this is
the fsdp-axis design BASELINE.json:9 asks for.
"""

from __future__ import annotations

import contextlib
import os
import time
from functools import partial
from typing import Any, Callable, Optional


@contextlib.contextmanager
def maybe_profile(profile_dir: Optional[str], log=print):
    """Wrap a block in a ``jax.profiler`` trace when ``profile_dir`` is set
    (SURVEY.md §5 tracing: workload-side profiling is jax.profiler's job).
    Callers must take timing measurements INSIDE the block — stop_trace()
    serializes the trace to disk and would otherwise pollute them."""
    if not profile_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(profile_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log(f"profile trace written to {profile_dir}")


def init_sharded_train_state(model_init: Callable, tx, mesh):
    """Returns ``(state, shardings)`` where state = {"params", "opt_state"},
    both sharded per the model's logical annotations (mu/nu like params,
    scalars replicated)."""
    from ..parallel import init_sharded

    def init_state(key):
        variables = model_init(key)
        params = variables["params"]  # still metadata-boxed
        return {"params": params, "opt_state": tx.init(params)}

    import jax

    return init_sharded(init_state, mesh, jax.random.key(int(os.environ.get("TPUJOB_SEED", "0"))))


def _env_int(name: str) -> int:
    try:
        return max(int(os.environ.get(name, "0")), 0)
    except ValueError:
        return 0


def data_plane_env() -> dict:
    """The full supervisor-injected ``spec.data_plane`` contract
    (runtime/env.py) as a dict — the ONE place every workload's
    ``--async-checkpoint`` / ``--prefetch`` / ``--prefetch-depth-max`` /
    ``--feed-autotune`` / ``--prefetch-workers`` flags read the spec
    knobs, so the env contract cannot drift per workload. Explicit
    flags win over these defaults."""
    return {
        "async_checkpoint": os.environ.get(
            "TPUJOB_ASYNC_CHECKPOINT", ""
        ).lower() in ("1", "true"),
        "prefetch": _env_int("TPUJOB_PREFETCH"),
        "prefetch_depth_max": _env_int("TPUJOB_PREFETCH_DEPTH_MAX"),
        "autotune": os.environ.get("TPUJOB_FEED_AUTOTUNE", "").lower()
        in ("1", "true"),
        "prefetch_workers": _env_int("TPUJOB_PREFETCH_WORKERS"),
    }


def data_plane_env_defaults() -> tuple:
    """Back-compat ``(async_checkpoint, prefetch)`` pair — see
    :func:`data_plane_env` for the full knob set."""
    dp = data_plane_env()
    return dp["async_checkpoint"], dp["prefetch"]


def add_feed_tuning_args(p) -> None:
    """The shared feed-pipeline argparse block (every workload with a
    ``--prefetch`` flag adds these three the same way — one definition
    so the flag/env contract cannot drift per workload). ``None``
    defaults mean "fall back to spec.data_plane env" — resolve with
    :func:`resolve_feed_tuning`."""
    import argparse as _ap

    p.add_argument(
        "--prefetch-depth-max", type=int, default=None, metavar="N",
        help="upper bound the feed's device lookahead may grow to "
        "(device-memory budget; default: spec.data_plane / "
        "TPUJOB_PREFETCH_DEPTH_MAX, else the static --prefetch depth)",
    )
    p.add_argument(
        "--feed-autotune", action=_ap.BooleanOptionalAction, default=None,
        help="let the feed resize its depth inside [1, --prefetch-depth-max] "
        "from the measured step-loop stall (grow fast, shrink slow — "
        "data/feed_autotune.py). Default: spec.data_plane / "
        "TPUJOB_FEED_AUTOTUNE",
    )
    p.add_argument(
        "--prefetch-workers", type=int, default=None, metavar="N",
        help="producer threads in the feed's sharded gather (batch order "
        "stays FIFO-deterministic; casts and transfers overlap). "
        "Default: spec.data_plane / TPUJOB_PREFETCH_WORKERS, else 1",
    )


def resolve_feed_tuning(args) -> dict:
    """Merge the :func:`add_feed_tuning_args` flags with the
    supervisor-injected spec defaults (explicit flags win) into the
    kwargs :class:`~pytorch_operator_tpu.data.device_prefetch.DevicePrefetcher`
    and :func:`open_image_feed` take."""
    env = data_plane_env()
    depth_max = (
        args.prefetch_depth_max
        if args.prefetch_depth_max is not None
        else env["prefetch_depth_max"]
    )
    autotune = (
        args.feed_autotune if args.feed_autotune is not None else env["autotune"]
    )
    workers = (
        args.prefetch_workers
        if args.prefetch_workers is not None
        else env["prefetch_workers"]
    )
    return {
        "prefetch_depth_max": max(depth_max, 0),
        "autotune": bool(autotune),
        "prefetch_workers": max(workers, 0),
    }


def probe_image_file(data_file: str):
    """Pre-model geometry probe: ``(meta, x_field_or_None)`` — the one
    place both benches read image shape from a packed file (full
    validation happens in :func:`open_image_feed`, which accepts the
    probed meta to avoid re-reading)."""
    from ..data import read_meta

    meta = read_meta(data_file)
    return meta, next((f for f in meta.fields if f.name == "x"), None)


def open_image_feed(
    data_file: str,
    *,
    batch: int,
    chunk: int,
    classes: int,
    mesh,
    square: bool = False,
    seed: int = 0,
    meta=None,
    prefetch: int = 0,
    prefetch_depth_max: int = 0,
    autotune: bool = False,
    prefetch_workers: int = 0,
):
    """Validate + open a packed image file and return ``(next_batches,
    loader)`` — the real-data feed both image benches share (one
    definition so validation/feed fixes cannot drift per bench).

    ``next_batches()`` returns ``chunk`` loader batches stacked
    ``[chunk, B, ...]`` as device arrays (bf16 images, i32 labels, one
    host transfer each). The loader hands out zero-copy views into a
    reused slot, so the copy into the stacked buffers is mandatory.
    Labels are range-checked against ``classes`` up front with a
    whole-file streaming scan — a first-chunk sample would miss
    out-of-range labels in later records, which one_hot to all-zero
    rows and silently deflate the loss (the same gap the token path's
    field_range scan closes). ``square=True`` additionally requires
    H == W (a model with learned position embeddings; ResNet is
    spatial-size-independent). Caller owns ``loader.close()`` —
    with ``prefetch > 0`` the returned "loader" is the device
    prefetcher facade (closing it closes the real loader too).

    ``prefetch=N`` moves the whole host side — loader pulls, stacking
    copy, and the ``device_put`` — onto a background feed pool with N
    stacked chunks of device lookahead (data/device_prefetch.py):
    ``next_batches()`` then just pops ready device arrays, zero
    transfers on the step path. ``prefetch_workers`` sizes the sharded
    gather (loader pulls stay serialized and FIFO; the stacking casts
    and transfers overlap across workers); ``prefetch_depth_max`` +
    ``autotune`` hand the depth to the stall-driven controller
    (data/feed_autotune.py).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from ..data import field_range, open_training_loader, read_meta
    from ..parallel.data import put_global

    if meta is None:
        meta = read_meta(data_file)
    names = [f.name for f in meta.fields]
    if "x" not in names or "y" not in names:
        raise ValueError(
            f"--data-file needs fields named 'x' (images) and 'y' (labels); "
            f"{data_file} has {names} (pack with pytorch_operator_tpu.data.pack)"
        )
    field_x = next(f for f in meta.fields if f.name == "x")
    if len(field_x.shape) != 3:
        raise ValueError(
            f"--data-file 'x' records must be HxWxC images; got shape "
            f"{field_x.shape}"
        )
    if square and field_x.shape[0] != field_x.shape[1]:
        raise ValueError(
            f"--data-file images must be square (H == W) for this model; "
            f"got {field_x.shape[0]}x{field_x.shape[1]}"
        )
    if meta.n_records < batch:
        raise ValueError(
            f"--data-file holds {meta.n_records} records < global batch {batch}"
        )
    lo, hi = field_range(data_file, meta, "y")
    if int(lo) < 0 or int(hi) >= classes:
        raise ValueError(
            f"--data-file labels span [{int(lo)}, {int(hi)}] but the model "
            f"head has {classes} classes (pass --classes)"
        )
    loader = open_training_loader(
        data_file, batch, seed=seed, processes=jax.process_count()
    )
    x_sh = NamedSharding(mesh, PartitionSpec(None, "dp"))

    def host_batches():
        # The SERIAL half (loader borrow contract): pull + same-dtype
        # slot copies only — a raw memcpy, so the serialized produce
        # turn stays short and the expensive work below can shard.
        raw = []
        for _ in range(chunk):
            _, _, fields = loader.next_batch()
            raw.append(
                (
                    np.array(fields["x"], copy=True),
                    np.array(fields["y"], copy=True),
                )
            )
        return raw

    def put_pair(raw):
        # The SHARDED half: f32 → bf16 casts, chunk stacking, and the
        # device transfer — with prefetch_workers > 1 these overlap
        # across producer threads while the next serial pull runs.
        sx = np.empty((chunk, batch) + field_x.shape, jnp.bfloat16)
        sy = np.empty((chunk, batch), np.int32)
        for i, (x, y) in enumerate(raw):
            sx[i] = x
            sy[i] = y
        return put_global(sx, x_sh), put_global(sy, x_sh)

    if prefetch > 0:
        from ..data.device_prefetch import DevicePrefetcher

        pf = DevicePrefetcher(
            host_batches,
            put=put_pair,
            depth=prefetch,
            depth_max=prefetch_depth_max or None,
            workers=max(prefetch_workers, 1),
            autotune=autotune,
        )

        class _Feed:
            """Caller-owned close handle: prefetcher first, then loader."""

            def stats(self):
                return pf.stats()  # feed-stall telemetry passthrough

            def close(self):
                pf.close()
                loader.close()

        return pf.get, _Feed()

    def next_batches():
        return put_pair(host_batches())

    return next_batches, loader


def make_optimizer(
    lr,
    *,
    schedule: str = "constant",
    warmup_steps: int = 0,
    decay_steps=None,
    grad_clip=None,
    weight_decay: float = 0.1,
    optimizer: str = "adamw",
):
    """The shared optimizer recipe (llama_train and bert_fsdp both use it —
    one definition so schedule/clipping fixes cannot drift per workload):
    optional linear-warmup + cosine decay, optional global-norm clipping.

    ``optimizer="adafactor"`` swaps AdamW's two full-size moment tensors
    for factored second-moment statistics (row+column vectors per
    matrix) — optimizer state drops from 2N to ~N/k floats, the
    standard memory lever at LM scale (an 8B model's Adam state alone
    is 64 GB f32; factored it is ~8 MB + params).
    """
    import optax

    if schedule == "cosine":
        sched = optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=lr,
            warmup_steps=max(warmup_steps, 1),
            decay_steps=max(decay_steps or warmup_steps + 1, warmup_steps + 1),
        )
    elif schedule == "constant":
        sched = lr
    else:
        raise ValueError(f"schedule={schedule!r} not in ('constant', 'cosine')")
    if optimizer == "adamw":
        tx = optax.adamw(sched, weight_decay=weight_decay)
    elif optimizer == "adafactor":
        # NO decoupled weight decay here: optax.adafactor applies
        # weight_decay_rate AFTER learning-rate scaling (a raw
        # fraction-per-step — passing the AdamW-style 0.1 would shrink
        # every param 10% per step, ~3000x the adamw-equivalent at
        # lr=3e-4, and keep decaying at full strength as a schedule
        # anneals). The classic Adafactor recipe trains without
        # decoupled decay; anyone needing it must size a raw per-step
        # rate deliberately, not inherit the AdamW knob.
        tx = optax.adafactor(sched)
    else:
        raise ValueError(
            f"optimizer={optimizer!r} not in ('adamw', 'adafactor')"
        )
    if grad_clip is not None:
        if grad_clip <= 0:
            raise ValueError(f"grad_clip must be positive, got {grad_clip}")
        tx = optax.chain(optax.clip_by_global_norm(grad_clip), tx)
    return tx


def make_lm_loss_fn(model, mesh, microbatches=None, include_aux=True):
    """Next-token cross-entropy ``loss_fn(params, tokens)`` — the shared
    objective behind the train step and held-out evaluation.
    ``include_aux=False`` drops the MoE load-balance term (evaluation:
    perplexity must be exp of the cross-entropy alone).

    When the model config sets ``xent_impl="chunked"``, the LM head matmul
    is fused into the loss via ops/chunked_xent.py — the model returns
    hidden states and no [B,S,V] logits tensor ever exists.

    When the mesh has a ``pp`` axis of extent > 1, the layer stack runs
    through the GPipe pipeline (models.llama.forward_pp) with
    ``microbatches`` microbatches (default 2 x pp extent) — numerically
    identical to the sequential forward, and composing with dp/fsdp on
    the same mesh.
    """
    import jax
    import optax

    from ..parallel import activation_rules

    cfg = getattr(model, "cfg", None)
    chunked = getattr(cfg, "xent_impl", "dense") == "chunked"
    aux_w = (
        float(getattr(cfg, "moe_aux_weight", 0.0) or 0.0) if include_aux else 0.0
    )
    pp = mesh.shape.get("pp", 1) > 1
    if pp:
        if not hasattr(model, "pp_forward"):
            raise ValueError(
                f"mesh has a pp axis but {type(model).__name__} defines no "
                "pp_forward hook (pipeline layering is model-owned)"
            )
        if aux_w > 0:
            raise ValueError(
                "moe_aux_weight is not supported on a pp mesh (the "
                "pipeline path bypasses flax sow collections)"
            )
        mb = microbatches or 2 * mesh.shape["pp"]

    def forward(params, tokens, return_hidden):
        """Returns (output, aux_loss) — aux is 0 unless the model sows
        MoE load-balance losses and moe_aux_weight > 0."""
        if pp:
            out = model.pp_forward(
                params, tokens,
                mesh=mesh, microbatches=mb, return_hidden=return_hidden,
            )
            return out, 0.0
        kwargs = {"return_hidden": True} if return_hidden else {}
        if aux_w > 0:
            out, mods = model.apply(
                {"params": params}, tokens, mutable=["losses"], **kwargs
            )
            import jax.numpy as jnp

            aux_leaves = jax.tree.leaves(mods.get("losses", {}))
            aux = (
                jnp.mean(jnp.stack([a.mean() for a in aux_leaves]))
                if aux_leaves
                else 0.0
            )
            return out, aux
        return model.apply({"params": params}, tokens, **kwargs), 0.0

    def loss_fn(params, tokens):
        if chunked:
            from ..ops.chunked_xent import chunked_softmax_xent

            with activation_rules(mesh):
                hidden, aux = forward(params, tokens, True)
            # Head access goes through the model (it owns its param naming).
            with jax.named_scope("loss"):
                w = model.head_kernel(params)
                h = hidden[:, :-1].reshape(-1, hidden.shape[-1])
                xent = chunked_softmax_xent(h, w, tokens[:, 1:].reshape(-1)).mean()
            return xent + aux_w * aux
        with activation_rules(mesh):
            logits, aux = forward(params, tokens, False)
        with jax.named_scope("loss"):
            xent = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]
            ).mean()
        return xent + aux_w * aux

    return loss_fn


def make_lm_train_step(
    model, tx, mesh, microbatches=None, pp_schedule="gpipe", donate=False,
    grad_accum=1,
):
    """Jitted LM train step. Objective semantics are
    :func:`make_lm_loss_fn`'s.

    ``donate=True`` donates the state (params + optimizer) into the step,
    letting XLA update it in place instead of holding a second copy —
    for the 0.3b config that is ~3.8 GB of HBM freed for batch. Safe
    with async checkpointing too: ``CheckpointManager.save(block=False)``
    snapshots the state to host BEFORE returning (async_writer.py), so
    the in-flight commit owns its own copy while the next step donates
    the original. (Callers driving orbax's own async machinery directly
    — without the snapshot — must still keep donation off.)

    ``grad_accum=N`` splits the global batch into N sequential
    microbatches inside ONE jitted step (``lax.scan`` over the leading
    split, mean of per-microbatch grads, one optimizer update) — the
    standard lever for global batches whose activations exceed HBM.
    Activation memory drops ~N-fold; the params-sized grad accumulator
    is the cost. Numerically equal to the unsplit step up to f32
    reassociation in the mean. Not composable with a pp mesh (the
    pipeline schedules already microbatch — use pp_microbatches).

    On a pp mesh, ``pp_schedule`` picks the pipeline execution:
    "gpipe" (autodiff's reverse schedule over the model's pp_forward —
    per-stage backward residency O(M·mb)) or "1f1b" (the model's fused
    pp_value_and_grad hook — residency O(P·mb), same numerics).
    """
    import jax
    import optax

    pp = mesh.shape.get("pp", 1) > 1
    # Validate BEFORE any schedule branch returns — grad_accum silently
    # ignored on the 1f1b path would be the same silent-knob trap the
    # remat-policy-without-remat guard exists for.
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
    if grad_accum > 1 and pp:
        raise ValueError(
            "grad_accum does not compose with a pp mesh — the pipeline "
            "schedules already microbatch (use pp_microbatches)"
        )
    if pp_schedule not in ("gpipe", "1f1b"):
        raise ValueError(
            f"pp_schedule={pp_schedule!r} not in ('gpipe', '1f1b')"
        )
    if pp_schedule == "1f1b" and not pp:
        # Silently falling back to the sequential step would let a
        # typo'd mesh spec masquerade as a 1F1B measurement.
        raise ValueError(
            "pp_schedule='1f1b' requested but the mesh has no pp axis "
            f"(mesh axes: {dict(mesh.shape)})"
        )
    if pp and pp_schedule == "1f1b":
        if not hasattr(model, "pp_value_and_grad"):
            raise ValueError(
                f"pp_schedule='1f1b' but {type(model).__name__} defines no "
                "pp_value_and_grad hook"
            )
        mb = microbatches or 2 * mesh.shape["pp"]

        @partial(jax.jit, donate_argnums=(0,) if donate else ())
        def train_step_1f1b(state, tokens):
            loss, grads = model.pp_value_and_grad(
                state["params"], tokens, mesh=mesh, microbatches=mb
            )
            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(
                    grads, state["opt_state"], state["params"]
                )
                params = optax.apply_updates(state["params"], updates)
            return {"params": params, "opt_state": opt_state}, loss

        return train_step_1f1b

    loss_fn = make_lm_loss_fn(model, mesh, microbatches)

    @partial(jax.jit, donate_argnums=(0,) if donate else ())
    def train_step(state, tokens):
        if grad_accum == 1:
            loss, grads = jax.value_and_grad(loss_fn)(state["params"], tokens)
        else:
            B = tokens.shape[0]
            if B % grad_accum:
                raise ValueError(
                    f"global batch {B} not divisible by grad_accum={grad_accum}"
                )
            mbs = tokens.reshape(grad_accum, B // grad_accum, *tokens.shape[1:])

            def body(carry, tb):
                loss_sum, grad_sum = carry
                loss, grads = jax.value_and_grad(loss_fn)(state["params"], tb)
                return (
                    loss_sum + loss,
                    jax.tree.map(lambda a, g: a + g, grad_sum, grads),
                ), None

            import jax.numpy as jnp

            # Accumulation is DELIBERATELY f32 (summing N bf16 microbatch
            # grads in bf16 loses low bits every step); the memory cost is
            # one f32-params-sized buffer regardless of param dtype. The
            # mean is cast back to the param dtype so the optimizer update
            # (and the params it produces) keep their configured dtype.
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"]
            )
            (loss_sum, grad_sum), _ = jax.lax.scan(body, (0.0, zeros), mbs)
            loss = loss_sum / grad_accum
            grads = jax.tree.map(
                lambda g, p: (g / grad_accum).astype(p.dtype),
                grad_sum,
                state["params"],
            )
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state["opt_state"], state["params"])
            params = optax.apply_updates(state["params"], updates)
        return {"params": params, "opt_state": opt_state}, loss

    return train_step


def make_lm_eval_step(model, mesh, microbatches=None):
    """Jitted held-out loss: ``eval_step(params, tokens) -> loss`` — the
    training cross-entropy WITHOUT the MoE aux term (no gradients flow,
    so load balancing is moot, and exp(eval loss) must be a true
    perplexity), no optimizer update."""
    import jax

    return jax.jit(make_lm_loss_fn(model, mesh, microbatches, include_aux=False))


class ProgressHeartbeat:
    """The ONE throttled steps/sec meter behind every live-telemetry
    heartbeat (throughput_loop and the loops that can't use it, e.g.
    mnist's epoch loop) — one definition so cadence and rate semantics
    cannot drift per workload.

    ``tick(step, loss_fn)`` fires at most every ``every_s`` seconds:
    calls ``loss_fn()`` (a real device fence), reports the rolling
    steps/sec over the interval MINUS any time the caller flagged via
    ``exclude()`` (checkpoint saves — the final throughput number
    excludes them, so the live meter must too or every save reads as a
    training stall), and returns the time spent reporting so callers
    timing their loop can exclude it. NB the FENCE is deliberately not
    excluded — it drains real queued compute, it just moves where the
    wait happens. With ``report=None`` every call is a free no-op
    (workloads pass None when no operator is listening — see
    ``rendezvous.progress_enabled`` — so standalone benchmark runs pay
    no fences and stay A/B-comparable with pre-telemetry numbers).
    """

    def __init__(self, report, every_s: float = 10.0, start_step: int = 0):
        self.report = report
        self.every_s = every_s
        self._t = time.time()
        self._step = start_step
        self._excl = 0.0

    def reset(self, step: int) -> None:
        """Restart the interval clock (call after compile/warmup — a
        clock started before the first-step compile would report the
        compile wait as a near-zero training rate)."""
        self._t, self._step, self._excl = time.time(), step, 0.0

    def exclude(self, dt: float) -> None:
        self._excl += dt

    def tick(self, step: int, loss_fn) -> float:
        if self.report is None or time.time() - self._t < self.every_s:
            return 0.0
        loss = loss_fn()  # fences: all work dispatched through `step` is done
        now = time.time()
        interval = max((now - self._t) - self._excl, 1e-9)
        self.report(step, loss, (step - self._step) / interval)
        done = time.time()
        self._t, self._step, self._excl = done, step, 0.0
        return done - now  # report time only; the fence was real compute


def heartbeat_reporter(report_progress, *, batch=None, n_dev=1, unit=None,
                       feed=None):
    """The shared ``ProgressHeartbeat`` → ``report_progress`` adapter:
    maps (step, loss, steps/sec) into a heartbeat record carrying the
    flight-recorder extras — interval-averaged step time (the
    supervisor's ``tpujob_step_time_seconds`` source) and, when ``feed``
    exposes ``stats()`` (a device prefetcher), the mean feed stall per
    get (the `tpujob top` feed-stall column)."""

    def report(step, loss, sps):
        kw = {}
        if batch is not None:
            kw["throughput"] = sps * batch / max(n_dev, 1)
            kw["unit"] = unit or "items/sec/chip"
        stats = getattr(feed, "stats", None)
        if stats is not None:
            try:
                s = stats()
                # The heartbeat carries the ROLLING-WINDOW stall: a live
                # burst must move the feed_stall_dominance rule now, not
                # after the lifetime average dilutes it. The cumulative
                # feed_stall_ms_avg stays in stats() for whole-run math.
                kw["feed_stall_ms"] = s.get(
                    "feed_stall_ms_recent", s["feed_stall_ms_avg"]
                )
            except Exception:
                # invariant: waived — feed-stall telemetry must never kill the step loop
                pass
        report_progress(
            step,
            loss=loss,
            steps_per_sec=sps,
            step_time_ms=1000.0 / sps if sps > 0 else None,
            **kw,
        )

    return report


def window_progress(report_progress, *, steps: int, batch: int, n_dev: int,
                    unit: str):
    """The rate math behind the image bench's per-window live meter:
    maps :func:`timed_windows`' ``(windows_done, windows_measured, dt)``
    into a progress record."""

    def progress(done, measured, dt):
        report_progress(
            done * steps,
            steps_per_sec=measured * steps / dt,
            throughput=batch * measured * steps / dt / n_dev,
            unit=unit,
        )

    return progress


def timed_windows(
    run_window, fence, *, windows, profile_dir=None, log=print, progress=None
):
    """The dual benchmark protocol of the image bench (resnet_bench):

    - Protocol A: fenced windows, min-time estimator (round-1 protocol;
      skipped when ``windows == 1`` — identical to B then — or when
      profiling, so the trace shows exactly the headline run).
    - Protocol B (headline): the same windows pipelined with depth-1
      lookahead — window i-1's token is fenced after dispatching window
      i, so the device never idles on a fence but the dispatch queue
      stays 1 deep (deeper queues hold one un-donatable train-state copy
      per in-flight dispatch; measured 3x slower on HBM-filling models).

    ``run_window()`` dispatches one window and returns a fence token;
    ``fence(token)`` performs a REAL host transfer on it. Returns
    ``(dt_min_window | None, dt_sustained_total, n_win)``.

    ``progress(windows_done, window_steps, dt_window)``, when given, is
    called after every fenced window (protocol A) and once after the
    sustained run with the aggregate — the live-telemetry hook the image
    benches use for the operator surface (controller/progress.py).
    """
    import math as _math
    import time as _time

    n_win = max(windows, 1)
    dt = _math.inf
    wins_done = 0  # ALL windows run real steps on the same state
    if not profile_dir and n_win > 1:
        for _ in range(n_win):
            t0 = _time.time()
            fence(run_window())
            dt_w = _time.time() - t0
            dt = min(dt, dt_w)
            wins_done += 1
            if progress is not None:
                progress(wins_done, 1, dt_w)
    with maybe_profile(profile_dir, log):
        t0 = _time.time()
        prev = None
        for _ in range(n_win):
            tok = run_window()
            if prev is not None:
                fence(prev)
            prev = tok
        fence(prev)
        # dt_sustained is taken here, before stop_trace() flushes.
        dt_sustained = _time.time() - t0
    wins_done += n_win
    if progress is not None:
        progress(wins_done, n_win, dt_sustained)
    if not _math.isfinite(dt):
        dt = None if profile_dir else dt_sustained / n_win
    return dt, dt_sustained, n_win


def throughput_loop(
    train_step,
    state,
    batches: Callable[[int], Any],
    *,
    steps: int,
    warmup: int,
    device_get,
    on_first_step: Optional[Callable[[Any, float], None]] = None,
    checkpoint_every: int = 0,
    save: Optional[Callable[[int, Any], None]] = None,
    start_step: int = 0,
    log=print,
    profile_dir: Optional[str] = None,
    progress: Optional[Callable[[int, float, float], None]] = None,
    progress_every_s: float = 10.0,
):
    """Run warmup + timed steps; returns (state, final_loss, steps_per_sec,
    end_step).

    ``device_get`` fetches the loss to the host, which is also the fence:
    the value exists only once every step dispatched before it has run.
    ``on_first_step(loss, seconds)`` is called once with the first step's
    fetched loss and its wall time, compilation included.
    Checkpoint-save time is excluded from the throughput window (the
    synthetic-benchmark methodology isolates compute).
    ``profile_dir`` wraps the timed window in a ``jax.profiler`` trace
    (SURVEY.md §5 tracing: workload-side profiling is jax.profiler's job),
    viewable with tensorboard/xprof.

    ``progress(step, loss, steps_per_sec)``, when given, is the live
    heartbeat for the operator surface: called at most every
    ``progress_every_s`` seconds with the rolling rate since the last
    heartbeat. Each heartbeat pays one device fence (to know the loss)
    — real queued compute draining, NOT excluded from the throughput
    window; only the report-write time is excluded (like checkpoint-save
    time). Pass ``progress=None`` when no operator is listening
    (``rendezvous.progress_enabled``) so standalone runs pay nothing.
    """
    step = start_step
    t0 = time.time()
    for i in range(max(warmup, 1)):
        state, loss = train_step(state, batches(step))
        step += 1
        if i == 0:
            first_loss = device_get(loss)
            first_s = time.time() - t0
            if on_first_step is not None:
                on_first_step(first_loss, first_s)
            log(f"first step (compile) +{first_s:.1f}s")
    device_get(loss)

    from .. import obs

    t_excluded = 0.0
    with maybe_profile(profile_dir, log):
        t0 = time.time()
        hb = ProgressHeartbeat(progress, progress_every_s, start_step=step)
        for _ in range(steps):
            with obs.span("step", cat="step", step=step):
                state, loss = train_step(state, batches(step))
            step += 1
            if checkpoint_every and save is not None and step % checkpoint_every == 0:
                device_get(loss)  # fence before leaving the hot loop
                t_save = time.time()
                with obs.span("save", cat="ckpt", step=step):
                    save(step, state)
                dt_save = time.time() - t_save
                t_excluded += dt_save
                hb.exclude(dt_save)  # the live meter excludes it too
            t_excluded += hb.tick(step, lambda: float(device_get(loss)))
        final_loss = float(device_get(loss))
        # dt is taken here, before stop_trace() flushes the trace to disk.
        dt = time.time() - t0 - t_excluded
    return state, final_loss, steps / dt, step
