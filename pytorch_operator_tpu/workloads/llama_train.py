"""Llama causal-LM training workload — fsdp×tp sharded, checkpointable.

Reference analog: the Llama-3-8B multi-host PyTorchJob target
(BASELINE.json:10). The real 8B config is selectable (``--config 8b``) and
the same code path is validated scaled-down (``--config tiny``) on the CPU
mesh in tests and in ``__graft_entry__.dryrun_multichip``.

Doubles as the preemption-recovery workload (BASELINE.json:11): with
``--checkpoint-every N`` it saves into the supervisor-injected per-job
checkpoint dir and resumes from the latest step on restart — kill a worker
mid-run and the restarted gang continues, not restarts.

Data is a synthetic affine-bigram stream (token[t+1] = (a·token[t]+b) mod V)
— structured enough that falling loss proves learning at a vocabulary a
run can cover, with zero input-pipeline cost.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

# The llama family's preset table, the same dict as models.llama.CONFIGS:
# the benchmark adds its preset `bench` through this name.
from ..models.llama import CONFIGS
from ..runtime import rendezvous


def synthetic_bigram_batch(batch: int, seq_len: int, vocab: int, step: int):
    """Deterministic learnable stream: next = (5·tok + 3) mod vocab."""
    import numpy as np

    rng = np.random.default_rng(step)
    first = rng.integers(0, vocab, size=(batch, 1), dtype=np.int64)
    toks = [first]
    for _ in range(seq_len - 1):
        toks.append((toks[-1] * 5 + 3) % vocab)
    return np.concatenate(toks, axis=1).astype(np.int32)


def run(
    *,
    config: str = "tiny",
    mesh_spec: str | None = None,
    batch_size: int = 8,
    seq_len: int = 128,
    steps: int = 20,
    warmup: int = 2,
    lr: float = 3e-4,
    optimizer: str = "adamw",
    lr_schedule: str = "constant",
    lr_warmup_steps: int = 0,
    lr_decay_steps: int | None = None,
    grad_clip: float | None = None,
    data_file: str | None = None,
    eval_file: str | None = None,
    eval_batches: int = 8,
    checkpoint_every: int = 0,
    async_checkpoint: bool = False,
    prefetch: int = 0,
    prefetch_depth_max: int = 0,
    feed_autotune: bool = False,
    prefetch_workers: int = 0,
    max_steps: int | None = None,
    remat: bool | None = None,
    remat_policy: str | None = None,
    param_dtype: str | None = None,
    n_layers: int | None = None,
    donate: bool | None = None,
    attn_impl: str | None = None,
    xent_impl: str | None = None,
    n_experts: int | None = None,
    moe_top_k: int | None = None,
    moe_dispatch: str | None = None,
    moe_capacity_factor: float | None = None,
    moe_aux_weight: float | None = None,
    pp_microbatches: int | None = None,
    pp_schedule: str = "gpipe",
    grad_accum: int = 1,
    preempt_at: int | None = None,
    profile_dir: str | None = None,
    log=print,
) -> dict:
    import jax
    import numpy as np
    import optax

    from ..checkpoint import CheckpointManager, job_checkpoint_dir
    from ..models import llama as llama_lib
    from ..parallel import make_mesh, named_sharding, put_global
    from .trainer import init_sharded_train_state, make_lm_train_step, throughput_loop

    over = {}
    if remat is not None:
        over["remat"] = remat
    if remat_policy is not None:
        over["remat_policy"] = remat_policy
    if attn_impl is not None:
        over["attn_impl"] = attn_impl
    if xent_impl is not None:
        over["xent_impl"] = xent_impl
    if n_experts is not None:
        over["n_experts"] = n_experts
    if moe_top_k is not None:
        over["moe_top_k"] = moe_top_k
    if moe_dispatch is not None:
        if moe_dispatch not in ("dense", "sparse"):
            raise ValueError(
                f"moe_dispatch={moe_dispatch!r} not in ('dense', 'sparse')"
            )
        over["moe_dispatch"] = moe_dispatch
    if moe_capacity_factor is not None:
        over["moe_capacity_factor"] = moe_capacity_factor
    if moe_aux_weight is not None:
        over["moe_aux_weight"] = moe_aux_weight
    if n_layers is not None:
        # Depth override for experiment sizing (e.g. the MoE A/B keeps
        # 0.3b WIDTH but fewer layers so E=16 experts fit one chip).
        over["n_layers"] = n_layers
    if param_dtype is not None:
        # bf16 params halve the checkpoint/state footprint — the lever
        # that fits the full 8B config's train state in host RAM for the
        # CPU-mesh end-to-end run (tests/test_llama8b_e2e.py) and on
        # smaller HBM parts. Grad accumulation still sums in f32
        # (trainer.py), and adafactor keeps its factored stats in f32.
        import jax.numpy as jnp

        allowed = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
        if param_dtype not in allowed:
            raise ValueError(
                f"param_dtype={param_dtype!r} not in {sorted(allowed)}"
            )
        over["param_dtype"] = allowed[param_dtype]
    cfg = getattr(llama_lib, CONFIGS[config])(**over)
    if remat_policy not in (None, "full") and not cfg.remat:
        # Silently measuring the no-remat path while the user believes
        # the selective policy is active is a benchmarking trap ('full'
        # is the inert default, so passing it without --remat measures
        # exactly what it says and is allowed).
        raise ValueError(
            f"--remat-policy {remat_policy} has no effect without --remat"
        )
    # Validate the routing config up front — otherwise a bad top_k only
    # surfaces as a ValueError deep inside model tracing.
    if cfg.n_experts > 0 and not (1 <= cfg.moe_top_k <= cfg.n_experts):
        raise ValueError(
            f"moe_top_k={cfg.moe_top_k} must lie in [1, n_experts="
            f"{cfg.n_experts}] — pass --moe-top-k to adjust the routing"
        )
    if cfg.moe_aux_weight > 0 and cfg.n_experts == 0:
        raise ValueError(
            "--moe-aux-weight needs a MoE model (pass --experts N); "
            "without experts no router exists, so the aux loss would be "
            "silently inert"
        )
    if cfg.n_experts > 0 and cfg.moe_dispatch == "sparse" and not cfg.moe_aux_weight:
        # LlamaConfig.__post_init__ raises a Python warning for library
        # users; repeat on the job-log surface, where training output goes.
        log(
            "[llama] WARNING: --moe-dispatch sparse with no "
            "--moe-aux-weight: an unbalanced router collapses onto a few "
            "experts and capacity-factor dispatch then DROPS most tokens. "
            "Pass --moe-aux-weight 1e-2."
        )

    n_dev = jax.device_count()
    import os

    mesh = make_mesh(mesh_spec or os.environ.get("TPUJOB_MESH", "fsdp=-1"))
    # The model consults the mesh for ring attention (sp axis) and MoE
    # expert dispatch (ep axis).
    if cfg.n_experts > 0 and mesh.shape.get("ep", 1) <= 1:
        log(
            f"[llama] WARNING: n_experts={cfg.n_experts} but the mesh has no "
            f"ep axis — experts run replicated on every device (dense "
            f'fallback). Use e.g. --mesh "dp=2,ep={cfg.n_experts}".'
        )
    model = llama_lib.Llama(cfg, mesh=mesh)
    batch = max(batch_size // n_dev, 1) * n_dev if batch_size % n_dev else batch_size
    log(
        f"[llama] config={config} d_model={cfg.d_model} layers={cfg.n_layers} "
        f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
        f"attn={cfg.attn_impl} batch={batch} seq={seq_len} "
        f"({jax.devices()[0].platform})"
    )

    if grad_accum > 1:
        if batch % grad_accum:
            raise ValueError(
                f"--grad-accum {grad_accum} must divide the global batch "
                f"{batch}"
            )
        data_extent = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
        if (batch // grad_accum) % data_extent:
            log(
                f"[llama] WARNING: per-microbatch batch "
                f"{batch // grad_accum} is not divisible by the data-"
                f"parallel extent {data_extent} — XLA will replicate "
                f"activations across the batch axes (SPMD 'involuntary "
                f"full rematerialization'). Make batch/grad_accum a "
                f"multiple of {data_extent} (e.g. batch="
                f"{grad_accum * data_extent * max(1, batch // (grad_accum * data_extent))})."
            )

    # Optimizer via the shared recipe helper. Cosine horizon default:
    # --max-steps when set (the GLOBAL step budget, correct across
    # checkpoint resumes — the restored optimizer count is global), else
    # this life's steps+warmup; a resumed run without --max-steps or
    # --lr-decay-steps would otherwise train its tail at LR ~0.
    from .trainer import make_optimizer

    tx = make_optimizer(
        lr,
        optimizer=optimizer,
        schedule=lr_schedule,
        warmup_steps=lr_warmup_steps,
        decay_steps=lr_decay_steps or max_steps or (steps + max(warmup, 1)),
        grad_clip=grad_clip,
        weight_decay=0.1,
    )
    t_init = time.time()
    state, _ = init_sharded_train_state(
        lambda k: model.init(k, np.zeros((1, seq_len), np.int32)), tx, mesh
    )
    n_params = sum(p.size for p in jax.tree.leaves(state["params"]))
    log(f"[llama] {n_params/1e6:.1f}M params, sharded init +{time.time()-t_init:.1f}s")

    # Donate the train state into the step (in-place update, ~one state
    # copy of HBM freed). Safe WITH --async-checkpoint too: save()
    # snapshots the state to host before returning, so the in-flight
    # commit reads its own copy while the next step donates the
    # original (checkpoint/async_writer.py).
    if donate is None:
        donate = True
    train_step = make_lm_train_step(
        model, tx, mesh, microbatches=pp_microbatches,
        pp_schedule=pp_schedule, donate=donate, grad_accum=grad_accum,
    )
    batch_sharding = named_sharding(mesh, "batch", "seq")

    # Fault injection (SURVEY.md §5 "fault injection = kill a worker
    # process in tests"): simulate a TPU preemption on the FIRST life of
    # this replica by dying with a retryable code (138 = 128+SIGUSR1)
    # mid-run; the supervisor's ExitCode policy gang-restarts and the
    # restarted life resumes from checkpoint.
    restart_count = int(os.environ.get("TPUJOB_RESTART_COUNT", "0"))

    def maybe_preempt(step: int):
        if preempt_at is not None and restart_count == 0 and step >= preempt_at:
            log(f"[llama] injected preemption at step {step} (exit 138)")
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(138)

    # Elastic in-place resize (controller/elastic.py): polled once per
    # step from the host side of the feed. jax.distributed cannot be
    # re-initialized in-process, so a survivor drains its host resources
    # and RE-EXECS with the new world's coordinates — same pid, same log
    # file, no scheduler round trip; the fresh main() re-joins at the new
    # coordinator and resumes from the last verified checkpoint. An
    # evicted replica exits 0 instead.
    train_world = rendezvous.world_from_env()

    def maybe_resize(step: int):
        sig = rendezvous.poll_resize(train_world)
        if sig is None:
            return
        log(
            f"[llama] resize generation {sig.generation} observed at "
            f"step {step}; draining for in-place re-join"
        )
        for drain in (
            lambda: prefetcher.close() if prefetcher is not None else None,
            lambda: loader.close() if loader is not None else None,
            lambda: mgr.close() if mgr is not None else None,
        ):
            try:
                drain()
            except Exception:
                # invariant: waived — best-effort drain on resize; a broken loader must not block the world exit
                pass
        rendezvous.exit_for_resize(sig)

    validated_files: dict = {}

    def open_token_file(path: str, flag: str, seed: int, do_open: bool = True):
        """Validate (once per path — the whole-file vocab scan is a full
        read) and optionally open a packed token file."""
        from ..data import field_range, open_training_loader, read_meta

        if path in validated_files:
            meta = validated_files[path]
            if not do_open:
                return None, meta
            return (
                open_training_loader(
                    path, batch, seed=seed, processes=jax.process_count()
                ),
                meta,
            )
        meta = read_meta(path)
        names = [f.name for f in meta.fields]
        if "tokens" not in names:
            raise ValueError(
                f"{flag} needs a 'tokens' field; {path} has {names} "
                f"(pack with pytorch_operator_tpu.data.pack --dataset text)"
            )
        f_tok = next(f for f in meta.fields if f.name == "tokens")
        if f_tok.shape[0] < seq_len:
            raise ValueError(
                f"{flag} records hold {f_tok.shape[0]} tokens < "
                f"--seq-len {seq_len}"
            )
        if f_tok.shape[0] > seq_len:
            log(
                f"[llama] WARNING: {flag} records hold {f_tok.shape[0]} "
                f"tokens; only the first {seq_len} of each are used "
                f"(--seq-len) — repack with --seq-len {seq_len} to use "
                f"the whole corpus"
            )
        if meta.n_records < batch:
            raise ValueError(
                f"{flag} holds {meta.n_records} records < global batch {batch}"
            )
        # Whole-file scan UP FRONT (memmap streaming pass): a per-batch
        # check would miss records outside the scanned batches, and XLA
        # clamps out-of-range embedding lookups (in BOTH directions)
        # silently.
        lo, hi = field_range(path, meta, "tokens")
        if int(lo) < 0 or int(hi) >= cfg.vocab_size:
            raise ValueError(
                f"{flag} token ids span [{int(lo)}, {int(hi)}] — outside "
                f"the model vocab [0, {cfg.vocab_size})"
            )
        validated_files[path] = meta
        if not do_open:
            return None, meta
        return (
            open_training_loader(
                path, batch, seed=seed, processes=jax.process_count()
            ),
            meta,
        )

    def next_tokens(ldr):
        _, _, fields = ldr.next_batch()
        return np.ascontiguousarray(fields["tokens"][:, :seq_len], np.int32)

    if eval_file:
        # Validate the eval file BEFORE spending any training compute —
        # a bad eval file must not destroy a finished run's output.
        if eval_batches < 1:
            raise ValueError(f"eval_batches must be >= 1, got {eval_batches}")
        open_token_file(eval_file, "--eval-file", seed=1, do_open=False)

    loader = None
    if data_file:
        loader, _ = open_token_file(data_file, "--data-file", seed=0)

        def host_batch(step: int):
            return next_tokens(loader)  # ascontiguousarray = slot copy

    else:

        def host_batch(step: int):
            return synthetic_bigram_batch(batch, seq_len, cfg.vocab_size, step)

    prefetcher = None
    # The try spans everything from here: a failure anywhere before or
    # during the loop (corrupt checkpoint, trainer validation) must not
    # leak the native loader's prefetch thread/mmap.
    try:
        # ---- resume (preemption recovery, BASELINE.json:11) ----
        start_step = 0
        mgr = None
        ckpt_dir = job_checkpoint_dir()
        if checkpoint_every and ckpt_dir is not None:
            # Staged async saves (fence-and-return; gather on the
            # writer's snapshot thread) need the device arrays alive
            # until the background gather reads them — a DONATING step
            # invalidates them, so donation keeps the eager PR-3
            # snapshot-at-submit path.
            mgr = CheckpointManager(
                ckpt_dir, staged=async_checkpoint and not donate
            )
            resumed = mgr.restore_or_none(state)
            if resumed is not None:
                start_step, state = resumed
                log(f"[llama] resumed from checkpoint at step {start_step}")
                if (
                    lr_schedule == "cosine"
                    and not lr_decay_steps
                    and not max_steps
                    and start_step > 0
                ):
                    # The cosine horizon defaulted to THIS life's
                    # steps+warmup, but the restored optimizer count is
                    # global (= start_step + this life's steps): the whole
                    # tail of this run sits past the decay horizon at
                    # LR ~= 0 and trains in place.
                    log(
                        "[llama] WARNING: resuming at step "
                        f"{start_step} with --lr-schedule cosine but no "
                        "--max-steps/--lr-decay-steps: the decay horizon "
                        f"defaulted to this life's {steps + max(warmup, 1)} "
                        "steps, so the resumed run trains at LR~0. Pass "
                        "--max-steps (global budget) or --lr-decay-steps."
                    )
                if loader is not None and start_step > 0:
                    # Fast-forward the data stream to where the previous
                    # life stopped (fixed seed ⇒ deterministic order):
                    # without this a resumed run would replay batches
                    # 0..start_step and diverge from an uninterrupted run.
                    for _ in range(start_step):
                        loader.next_batch()
                    log(
                        f"[llama] data stream fast-forwarded "
                        f"{start_step} batches"
                    )

        if max_steps is not None:
            steps = max(min(steps, max_steps - start_step - max(warmup, 1)), 0)

        # The device feed is built AFTER resume: the prefetcher's step
        # counter starts where the loop will (start_step), and the
        # data-file fast-forward above must finish before a background
        # thread starts pulling the loader.
        if prefetch > 0:
            import itertools

            from ..data.device_prefetch import DevicePrefetcher

            _feed_steps = itertools.count(start_step)
            prefetcher = DevicePrefetcher(
                lambda: host_batch(next(_feed_steps)),
                put=lambda toks: put_global(toks, batch_sharding),
                depth=prefetch,
                depth_max=prefetch_depth_max or None,
                workers=max(prefetch_workers, 1),
                autotune=feed_autotune,
            )

            def batches(step: int):
                maybe_preempt(step)
                maybe_resize(step)
                # Already device-resident: batch step+prefetch is being
                # transferred on the feed thread while this step runs.
                return prefetcher.get()

        else:

            def batches(step: int):
                maybe_preempt(step)
                maybe_resize(step)
                return put_global(host_batch(step), batch_sharding)

        first = {}

        def on_first(loss, seconds):
            first.update(loss=float(loss), seconds=seconds)
            rendezvous.report_first_step(start_step)

        with mesh:
            state, final_loss, steps_per_sec, end_step = throughput_loop(
                train_step,
                state,
                batches,
                steps=steps,
                warmup=warmup,
                device_get=lambda x: jax.device_get(x),
                on_first_step=on_first,
                checkpoint_every=checkpoint_every,
                # Async saves overlap the orbax write with the next training
                # steps — safe ONLY because the donate guard above forces
                # donate=False under --async-checkpoint (a donating step
                # would invalidate the buffers mid-save); mgr.close()/the
                # final save below still commit everything before exit.
                # Blocking is the default — preemption tests need the
                # just-saved step to be durable.
                save=(
                    (lambda s, st: mgr.save(s, st, block=not async_checkpoint))
                    if mgr is not None
                    else None
                ),
                start_step=start_step,
                log=lambda m: log(f"[llama] {m}"),
                profile_dir=profile_dir,
                # Live heartbeat for `tpujob describe` / /metrics gauges
                # (None standalone: no listener, no telemetry fences).
                progress=(
                    (
                        lambda s, l, sps: rendezvous.report_progress(
                            s, loss=l, steps_per_sec=sps,
                            throughput=sps * batch * seq_len / n_dev,
                            unit="tokens/sec/chip",
                        )
                    )
                    if rendezvous.progress_enabled()
                    else None
                ),
            )
    finally:
        if prefetcher is not None:
            prefetcher.close()
        if loader is not None:
            loader.close()
    if mgr is not None:
        if mgr.latest_step() != end_step:
            mgr.save(end_step, state)
        mgr.close()

    tokens_per_sec = steps_per_sec * batch * seq_len
    per_chip = tokens_per_sec / n_dev
    rendezvous.report_metrics(
        end_step,
        tokens_per_sec=tokens_per_sec,
        tokens_per_sec_per_chip=per_chip,
        final_loss=final_loss,
    )
    log(
        f"[llama] {steps} steps: {tokens_per_sec:,.0f} tokens/sec "
        f"({per_chip:,.0f}/chip), final loss {final_loss:.3f}"
    )
    result = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "tokens/sec/chip",
        "config": config,
        "params_m": round(n_params / 1e6, 1),
        "first_loss": round(first["loss"], 4),
        "first_step_s": round(first["seconds"], 2),
        "final_loss": round(final_loss, 4),
        "start_step": start_step,
        "end_step": end_step,
        "devices": n_dev,
        "n_layers": cfg.n_layers,
        "d_model": cfg.d_model,
        **rendezvous.report_device(),
    }
    if cfg.n_experts > 1:
        # FLOPs-active parameter count for honest MoE MFU: sparse
        # dispatch computes ~top_k/E of the expert weights per token
        # (capacity padding excluded — it inflates buffers, not useful
        # FLOPs); dense dispatch computes every expert.
        from jax import tree_util

        expert_params = sum(
            leaf.size
            for path, leaf in tree_util.tree_flatten_with_path(
                state["params"]
            )[0]
            if any(
                getattr(k, "key", None) in ("w_in", "w_out") for k in path
            )
        )
        frac = (
            cfg.moe_top_k / cfg.n_experts
            if cfg.moe_dispatch == "sparse"
            else 1.0
        )
        result["n_experts"] = cfg.n_experts
        result["moe_dispatch"] = cfg.moe_dispatch
        result["active_params_m"] = round(
            (n_params - expert_params + expert_params * frac) / 1e6, 1
        )

    if eval_file:
        # Held-out evaluation: same objective as training (shared
        # make_lm_loss_fn), fixed deterministic batch order, no updates.
        from .trainer import make_lm_eval_step

        eval_loader, eval_meta = open_token_file(eval_file, "--eval-file", seed=1)
        try:
            eval_step = make_lm_eval_step(model, mesh, microbatches=pp_microbatches)
            n_eval = max(
                1, min(eval_batches, eval_meta.n_records // batch)
            )
            losses = []
            with mesh:
                for _ in range(n_eval):
                    losses.append(
                        float(
                            jax.device_get(
                                eval_step(
                                    state["params"],
                                    put_global(
                                        next_tokens(eval_loader), batch_sharding
                                    ),
                                )
                            )
                        )
                    )
        finally:
            eval_loader.close()
        eval_loss = sum(losses) / len(losses)
        ppl = math.exp(min(eval_loss, 30.0))
        rendezvous.report_metrics(
            end_step, eval_loss=eval_loss, eval_perplexity=ppl
        )
        log(
            f"[llama] eval: loss {eval_loss:.4f} (ppl {ppl:.1f}) over "
            f"{n_eval} held-out batch(es)"
        )
        result["eval_loss"] = round(eval_loss, 4)
        result["eval_perplexity"] = round(ppl, 2)
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", choices=sorted(CONFIGS), default="tiny")
    p.add_argument("--mesh", default=None, help='e.g. "fsdp=4,tp=2" (default: TPUJOB_MESH or fsdp=-1)')
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument(
        "--lr-schedule", choices=("constant", "cosine"), default="constant",
        help="cosine = linear warmup to --lr then cosine decay over "
        "--lr-decay-steps (default: the run length)",
    )
    p.add_argument("--lr-warmup-steps", type=int, default=0)
    p.add_argument("--lr-decay-steps", type=int, default=None)
    p.add_argument(
        "--grad-clip", type=float, default=None,
        help="clip gradients to this global norm (standard LM recipe: 1.0)",
    )
    p.add_argument(
        "--data-file", default=None,
        help="train from packed token records via the prefetch loader "
        "(pack any text file byte-level with pytorch_operator_tpu.data."
        "pack --dataset text); default: synthetic bigram stream",
    )
    p.add_argument(
        "--eval-file", default=None,
        help="held-out packed token file: report eval loss + perplexity "
        "after training (same objective, no updates)",
    )
    p.add_argument(
        "--eval-batches", type=int, default=8,
        help="max held-out batches to average over",
    )
    p.add_argument("--checkpoint-every", type=int, default=0)
    p.add_argument(
        "--async-checkpoint", action="store_true",
        help="overlap checkpoint commits with training: the step loop "
        "pays only the host snapshot; the write + checksum sidecar land "
        "on a background commit thread (verified at commit). Committed "
        "by job end; a preemption may lose the in-flight save and "
        "resume one interval earlier. Default: spec.data_plane / "
        "TPUJOB_ASYNC_CHECKPOINT",
    )
    p.add_argument(
        "--prefetch", type=int, default=None, metavar="DEPTH",
        help="double-buffered device feed: keep DEPTH batches "
        "device-resident ahead of the step loop (host→device transfer "
        "overlaps compute on a feed thread; 0 = inline). Default: "
        "spec.data_plane / TPUJOB_PREFETCH",
    )
    p.add_argument("--max-steps", type=int, default=None)
    p.add_argument(
        "--optimizer", choices=("adamw", "adafactor"), default="adamw",
        help="adafactor: factored second moments — optimizer state ~N/k "
        "floats instead of AdamW's 2N (the memory lever at LM scale)",
    )
    p.add_argument(
        "--grad-accum", type=int, default=1,
        help="split the global batch into N sequential microbatches inside "
        "one jitted step (mean grads, one optimizer update): ~N-fold less "
        "activation memory for the same global batch",
    )
    p.add_argument("--remat", action="store_true")
    p.add_argument(
        "--remat-policy", choices=("full", "dots"), default=None,
        help="with --remat: 'full' recomputes the whole block in backward "
        "(min HBM); 'dots' saves the projection/MLP GEMM outputs so "
        "backward skips recomputing the MXU-bound work (more HBM)",
    )
    p.add_argument(
        "--donate", action=argparse.BooleanOptionalAction, default=None,
        help="donate the train state into the jitted step (in-place "
        "update, ~one state copy of HBM freed). Default: on — safe "
        "even with --async-checkpoint, whose save snapshots the state "
        "to host before the next step can donate it",
    )
    p.add_argument(
        "--attn-impl", choices=("dense", "flash", "ring", "ulysses"),
        default=None,
        help="attention implementation (flash = pallas blockwise kernel; "
        "ring = sequence-parallel K/V rotation over sp; ulysses = "
        "all-to-all head/seq swap over sp — 2 collectives vs ring's P, "
        "full-S scores per local head)",
    )
    p.add_argument(
        "--xent", choices=("dense", "chunked"), default=None, dest="xent_impl",
        help="loss implementation (chunked = fused head+loss over vocab "
        "chunks, no [B,S,V] logits tensor)",
    )
    p.add_argument(
        "--experts", type=int, default=None, dest="n_experts",
        help="mixture-of-experts MLP with this many experts, sharded over "
        "the mesh's ep axis (falls back to replicated dense compute, with "
        "a warning, when the mesh has no ep axis); default dense SwiGLU",
    )
    p.add_argument(
        "--moe-top-k", type=int, default=None, dest="moe_top_k",
        help="experts routed per token (default 2); must be <= --experts",
    )
    p.add_argument(
        "--moe-dispatch", choices=("dense", "sparse"), default=None,
        dest="moe_dispatch",
        help="expert dispatch: dense (exact, FLOPs scale with experts) or "
        "sparse (capacity-factor GShard dispatch, FLOPs scale with top_k; "
        "over-capacity tokens dropped — prefer from 16 experts up)",
    )
    p.add_argument(
        "--moe-capacity-factor", type=float, default=None,
        dest="moe_capacity_factor",
        help="sparse dispatch per-expert capacity multiplier (default "
        "1.25); higher drops fewer tokens, costs more FLOPs",
    )
    p.add_argument(
        "--moe-aux-weight", type=float, default=None, dest="moe_aux_weight",
        help="Switch-style load-balancing aux loss weight (typical 0.01; "
        "default 0 = off); spreads the router across experts",
    )
    p.add_argument(
        "--layers", type=int, default=None, dest="n_layers",
        help="override the config's layer count (experiment sizing)",
    )
    p.add_argument(
        "--param-dtype", choices=("float32", "bfloat16"), default=None,
        dest="param_dtype",
        help="parameter storage dtype (default float32); bfloat16 halves "
        "param/grad/checkpoint bytes — the memory lever for 8B+ configs",
    )
    p.add_argument(
        "--pp-microbatches", type=int, default=None,
        help="GPipe microbatch count when the mesh has a pp axis "
        "(default 2 x pp extent; must be a multiple of it)",
    )
    p.add_argument(
        "--pp-schedule", choices=("gpipe", "1f1b"), default="gpipe",
        help="pipeline schedule on a pp mesh: gpipe (autodiff reverse "
        "schedule, backward holds all M microbatch residuals per stage) "
        "or 1f1b (fused one-forward-one-backward scan, residency bounded "
        "by stage depth; identical numerics)",
    )
    p.add_argument(
        "--preempt-at", type=int, default=None,
        help="fault injection: die with a retryable exit code at this step "
        "on the replica's first life (simulated TPU preemption)",
    )
    p.add_argument(
        "--preempt-index", default=None,
        help="restrict --preempt-at to the replicas whose "
        "TPUJOB_REPLICA_INDEX is in this comma-separated list (replicas "
        "of one spec share args; this lets a chosen subset of the gang "
        "preempt — e.g. two of three workers so an fsdp=4 world shrinks "
        "to the still-divisible fsdp=2 — instead of all of them)",
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

    env_async, env_prefetch = data_plane_env_defaults()
    feed_tuning = resolve_feed_tuning(args)
    world = rendezvous.initialize_from_env()
    result = run(
        config=args.config,
        mesh_spec=args.mesh,
        batch_size=args.batch_size,
        seq_len=args.seq_len,
        steps=args.steps,
        warmup=args.warmup,
        lr=args.lr,
        optimizer=args.optimizer,
        lr_schedule=args.lr_schedule,
        lr_warmup_steps=args.lr_warmup_steps,
        lr_decay_steps=args.lr_decay_steps,
        grad_clip=args.grad_clip,
        data_file=args.data_file,
        eval_file=args.eval_file,
        eval_batches=args.eval_batches,
        checkpoint_every=args.checkpoint_every,
        async_checkpoint=args.async_checkpoint or env_async,
        prefetch=args.prefetch if args.prefetch is not None else env_prefetch,
        prefetch_depth_max=feed_tuning["prefetch_depth_max"],
        feed_autotune=feed_tuning["autotune"],
        prefetch_workers=feed_tuning["prefetch_workers"],
        max_steps=args.max_steps,
        remat=True if args.remat else None,
        remat_policy=args.remat_policy,
        param_dtype=args.param_dtype,
        n_layers=args.n_layers,
        donate=args.donate,
        attn_impl=args.attn_impl,
        xent_impl=args.xent_impl,
        n_experts=args.n_experts,
        moe_top_k=args.moe_top_k,
        moe_dispatch=args.moe_dispatch,
        moe_capacity_factor=args.moe_capacity_factor,
        moe_aux_weight=args.moe_aux_weight,
        pp_microbatches=args.pp_microbatches,
        pp_schedule=args.pp_schedule,
        grad_accum=args.grad_accum,
        preempt_at=(
            None
            if args.preempt_index is not None
            and int(os.environ.get("TPUJOB_REPLICA_INDEX", "0"))
            not in {
                int(s) for s in str(args.preempt_index).split(",") if s.strip()
            }
            else args.preempt_at
        ),
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
    # Deterministic multi-process teardown (never returns for real
    # worlds): jax's implicit atexit teardown intermittently segfaults
    # a COMPLETED replica, and that 139 is retryable — it would burn a
    # restart re-running a finished life.
    rendezvous.finalize(world)
    return 0


if __name__ == "__main__":
    sys.exit(main())
