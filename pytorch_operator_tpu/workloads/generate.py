"""Autoregressive generation with a KV cache (LM decode path).

Reference analog: none (the reference is a training operator) — this is
the completeness piece a framework user expects next to the training
stack. TPU-first shape: ONE jitted program runs prefill (the whole
prompt written into the cache in a single pass) plus a ``lax.scan`` over
decode steps; the cache is donated and updated in place
(``dynamic_update_slice``), every step is the same static-shape XLA
program, and sampling (greedy or temperature) happens on device — the
host only sees the final token block.

No tokenizer ships in this environment (no network), so the CLI drives
synthetic prompts; the correctness harness (tests/test_generate.py)
proves cache-decode greedy output equals the training model's
full-forward argmax rollout token for token.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

from ..runtime import rendezvous


def make_generate(
    model,
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
):
    """Build a jitted ``generate(params, cache, prompt, rng) ->
    (tokens [B, max_new_tokens], cache)``. ``model`` must be built with
    ``cfg.decode=True``; greedy when ``temperature == 0``.

    Rides :func:`models.llama.decode_forward` — the unrolled serving
    path whose only per-step cache writes are one token-slice per layer
    (the flax scan-lifted path rewrites every slab every step; see that
    docstring). ``params`` may contain
    :class:`ops.quantize.QuantizedTensor` leaves (weight-only int8):
    each layer's slice is dequantized at its use site, so the weights
    stay int8 in HBM and the convert+scale fuses into each matmul's
    operand read.

    CONTRACT (inherited from ``Llama._decode_attend`` at the default
    ``decode_per_row=False``): every prompt row must occupy the same
    positions — i.e. an unpadded, equal-length prompt batch (the cache
    write offset reads row 0). Ragged batches must be bucketed to equal
    length here, generated row-by-row, or decoded through a
    ``decode_per_row=True`` model at per-row positions (what a
    continuous-batching serving engine does; see
    tests/test_serving_batch.py for the parity contract). Set
    ``TPUJOB_DEBUG_CHECKS=1`` to assert the contract at runtime.
    """
    import functools

    import jax
    import jax.numpy as jnp

    from ..models.llama import decode_forward
    from ..ops.sampling import make_sampler

    # Shared with the serving engine (ops/sampling.py): greedy / T /
    # top-k / nucleus off one descending sort, knobs validated up front.
    sample = make_sampler(temperature, top_k, top_p)

    def last_logits(params, hidden):
        # Head matmul on the LAST position only: prefill would otherwise
        # materialize [B, prompt_len, vocab] f32 logits (~2 GB at the
        # 0.3b bench config) just to sample one token.
        w = model.head_kernel(params)
        return hidden[:, -1].astype(jnp.float32) @ w.astype(jnp.float32)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def generate(params, cache, prompt, rng):
        B, Sp = prompt.shape
        L = model.cfg.max_decode_len
        if Sp + max_new_tokens > L:
            # Trace-time guard: dynamic_update_slice would silently CLAMP
            # an overflowing write to the last cache slot and corrupt the
            # rollout instead of failing.
            raise ValueError(
                f"prompt_len {Sp} + max_new_tokens {max_new_tokens} "
                f"exceeds cfg.max_decode_len {L}"
            )
        hidden, cache = decode_forward(model, params, cache, prompt)
        rng, k = jax.random.split(rng)
        tok = sample(last_logits(params, hidden), k)

        def step(carry, _):
            cache, tok, pos, rng = carry
            positions = jnp.broadcast_to(pos, (B, 1))
            h, cache = decode_forward(
                model, params, cache, tok[:, None], positions
            )
            rng, k = jax.random.split(rng)
            nxt = sample(last_logits(params, h), k)
            return (cache, nxt, pos + 1, rng), tok

        (cache, last, _, _), toks = jax.lax.scan(
            step,
            (cache, tok, jnp.int32(Sp), rng),
            None,
            length=max_new_tokens - 1,
        )
        out = jnp.concatenate([toks.swapaxes(0, 1), last[:, None]], axis=1)
        return out, cache

    return generate


def init_cache(model, batch: int, prompt_len: int = 0):
    """Zero KV cache for ``model`` (cfg.decode=True) in the
    :func:`models.llama.decode_forward` flat per-layer layout.
    ``prompt_len`` is accepted for signature compatibility; the cache
    is statically sized by ``cfg.max_decode_len`` alone."""
    from ..models.llama import init_decode_cache

    return init_decode_cache(model.cfg, batch)


def load_params(
    cfg,
    *,
    config: str,
    restore: str | None = None,
    quantize: str | None = None,
    init_host: bool = False,
    compare_unquantized: bool = False,
    seed: int = 0,
    log=print,
    tag: str = "generate",
):
    """Build the serving param tree for ``cfg`` — shared by the
    single-stream generate workload and the serving engine workload.

    Init-or-restore (params-only partial restore with the full-structure
    shape check), optional host-side init for trees beyond device HBM,
    optional int8 weight-only quantization, and a one-time device
    commit. Returns ``(params, params_fp, n_params, weight_bytes,
    restored_step)`` where ``params_fp`` is the unquantized control
    (only when ``compare_unquantized``)."""
    import contextlib

    import jax
    import numpy as np

    if init_host and not quantize:
        # Host init exists exactly for models whose full-precision tree
        # does not fit device HBM (8B f32 = 32 GB > 16 GB); without
        # quantization the transferred tree wouldn't fit either — and
        # the tree would stay committed to the CPU backend. Lives HERE
        # so every caller (generate, serve, bench) gets the guard.
        raise ValueError("init_host requires quantize='int8'")

    # The model's own seeded init (models/serving.py): the llama family's
    # is the training model's float32 tree in one program, which the
    # quantisation below then shrinks; a family that serves its weights as
    # they are makes them in the serving dtype, a layer at a time.
    model = dataclasses.replace(cfg, decode=True).serving_model()
    make_params = model.init_params

    restored_step = None
    if restore is not None:
        # Serve a TRAINED checkpoint (the train -> checkpoint -> serve
        # journey): restore the train state as saved — no optimizer
        # reconstruction — and keep only its params.
        from ..checkpoint.manager import CheckpointManager

        # Partial restore of ONLY the params subtree: the saved
        # optimizer state is ~2x params bytes for adamw, and even
        # transient full-state residency would OOM the host at 8B
        # (~96 GB state on a ~125 GB host) — the optimizer shards are
        # never read at all (ADVICE r4 medium).
        with CheckpointManager(restore, create=False) as mgr_:
            try:
                restored_step, params = mgr_.restore_subtree("params")
            except KeyError as e:
                raise ValueError(
                    f"checkpoint under {restore} has no 'params': {e}"
                ) from None
        # The trainer's tree as the model's own init arranges it (the llama
        # family: its layers a tree each; views of the host arrays).
        params = model.arrange(params)
        # Config check against the FULL expected structure (ADVICE r4):
        # an embedding-only check lets a wrong-n_layers/d_ff/n_heads
        # checkpoint through to an opaque stacked-param tracing error.
        # Shapes only — a bf16-trained checkpoint must still serve.
        import jax.tree_util as jtu

        expected = jax.eval_shape(make_params, jax.random.key(0))
        exp = {
            jtu.keystr(p): tuple(l.shape)
            for p, l in jtu.tree_flatten_with_path(expected)[0]
        }
        got = {
            jtu.keystr(p): tuple(np.shape(l))
            for p, l in jtu.tree_flatten_with_path(params)[0]
        }
        for path in sorted(exp.keys() | got.keys()):
            if exp.get(path) != got.get(path):
                raise ValueError(
                    f"checkpoint params don't match --config {config}: "
                    f"first mismatch at {path}: checkpoint has "
                    f"{got.get(path, 'nothing')}, config expects "
                    f"{exp.get(path, 'nothing')}"
                )
        log(
            f"[{tag}] restored params from {restore} "
            f"(step {restored_step})"
        )
    else:
        # init_host: full-precision init + quantization on the HOST CPU
        # backend (the 8B tree is 32 GB f32 — twice this chip's HBM),
        # then only the int8 tree crosses to the device. This is the
        # path that puts Llama-3-8B decode on ONE 16 GB v5e chip.
        init_ctx = (
            jax.default_device(jax.local_devices(backend="cpu")[0])
            if init_host
            else contextlib.nullcontext()
        )
        with init_ctx:
            params = make_params(jax.random.key(seed))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    src = (
        f"trained checkpoint, step {restored_step}"
        if restored_step is not None
        else "random init — no tokenizer here"
    )
    log(f"[{tag}] {n_params / 1e6:.1f}M params ({src})")

    weight_bytes = None
    params_fp = None
    if quantize:
        from ..ops import quantize as quant_lib

        t0 = time.time()
        if init_host:
            with jax.default_device(jax.local_devices(backend="cpu")[0]):
                qparams = quant_lib.quantize_tree(params)
            del params
            qparams = jax.device_put(qparams, jax.devices()[0])
        else:
            if compare_unquantized:
                params_fp = params
                if restored_step is not None:
                    # Restored trees are host numpy: commit the control
                    # to the device once, or its timed reps would pay
                    # per-call weight upload and inflate int8_speedup.
                    params_fp = jax.block_until_ready(
                        jax.device_put(params_fp, jax.devices()[0])
                    )
            qparams = jax.jit(quant_lib.quantize_tree)(params)
        qparams = jax.block_until_ready(qparams)
        params = qparams
        weight_bytes = quant_lib.tree_bytes(params)
        log(
            f"[{tag}] int8 weight-only quantization: {weight_bytes / 1e9:.2f} "
            f"GB on device (f32 would be {4 * n_params / 1e9:.2f} GB) "
            f"+{time.time() - t0:.1f}s"
        )
    elif restored_step is not None:
        # Restored params are host numpy; committed to the device ONCE
        # here, or every jitted call (compile + each timed rep) would
        # re-upload the whole tree and the reported tok/s would include
        # per-call weight transfer (ADVICE r4). The quantize branch gets
        # this for free from jit(quantize_tree).
        params = jax.block_until_ready(
            jax.device_put(params, jax.devices()[0])
        )
    return params, params_fp, n_params, weight_bytes, restored_step


def run(
    *,
    config: str = "tiny",
    batch_size: int = 8,
    prompt_len: int = 64,
    max_new_tokens: int = 64,
    max_decode_len: int | None = None,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    quantize: str | None = None,
    kv_quantize: str | None = None,
    init_host: bool = False,
    compare_unquantized: bool = False,
    restore: str | None = None,
    seed: int = 0,
    log=print,
) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import llama as llama_lib

    if quantize not in (None, "int8"):
        raise ValueError(f"quantize={quantize!r} not in (None, 'int8')")
    if compare_unquantized and (not quantize or init_host):
        # The same-session A/B needs both trees resident — exactly what
        # init_host models cannot do.
        raise ValueError(
            "compare_unquantized requires quantize and not init_host"
        )

    cfg = getattr(llama_lib, llama_lib.CONFIGS[config])(
        decode=True,
        # The cache is statically sized by max_decode_len; overriding it
        # beyond prompt+new measures serving at a context budget without
        # generating the whole window (the step cost is L-dependent
        # regardless of fill — static shapes).
        max_decode_len=max_decode_len or (prompt_len + max_new_tokens),
        # attn_impl stays the config's default (flash for the llama
        # configs): prefill runs causal self-attention over the prompt
        # (blockwise — long prompts don't materialize scores against
        # the cache budget); decode steps attend against the cache.
        quantize=quantize,
        kv_quantize=kv_quantize,
    )
    model = llama_lib.Llama(cfg)
    log(
        f"[generate] config={config} d_model={cfg.d_model} "
        f"layers={cfg.n_layers} batch={batch_size} prompt={prompt_len} "
        f"new={max_new_tokens} T={temperature} "
        f"({jax.devices()[0].platform})"
    )

    params, params_fp, n_params, weight_bytes, restored_step = load_params(
        cfg, config=config, restore=restore, quantize=quantize,
        init_host=init_host, compare_unquantized=compare_unquantized,
        seed=seed, log=log,
    )

    prompt = jnp.asarray(
        np.random.default_rng(seed).integers(
            0, cfg.vocab_size, (batch_size, prompt_len)
        ),
        jnp.int32,
    )
    gen = make_generate(
        model, max_new_tokens=max_new_tokens, temperature=temperature,
        top_k=top_k, top_p=top_p,
    )

    def timed(run_params, label):
        """Compile, then best-of-3, each rep ending in a device_get
        fence. Reps REUSE the returned (donated-in-place) cache:
        every readable slot is rewritten before use (the
        garbage-cannot-leak test pins that reuse and fresh zeros decode
        identically), and a fresh cache per rep would double-allocate
        next to the in-flight donated one — measured RESOURCE_EXHAUSTED
        at the 8B/b8/L=8192 point where cache+weights fill the chip."""
        cache = init_cache(model, batch_size, prompt_len)
        t0 = time.time()
        toks, cache = gen(run_params, cache, prompt, jax.random.key(seed))
        jax.block_until_ready(toks)
        log(f"[generate] {label}: compile + first generation +{time.time() - t0:.1f}s")
        best = float("inf")
        for rep in range(3):
            t0 = time.time()
            toks, cache = gen(run_params, cache, prompt, jax.random.key(seed + 1 + rep))
            int(jax.device_get(toks[0, -1]))
            best = min(best, time.time() - t0)
        return best

    dt = timed(params, quantize or "full-precision")
    dt_fp = None
    if params_fp is not None:
        # Same-session A/B: the unquantized control through the same
        # jitted program (a distinct compile — the param pytree differs).
        dt_fp = timed(params_fp, "full-precision control")
    new_tokens = batch_size * max_new_tokens
    tps = new_tokens / dt
    n_dev = jax.device_count()
    rendezvous.report_first_step(0)
    rendezvous.report_metrics(
        max_new_tokens, decode_tokens_per_sec=tps,
        decode_tokens_per_sec_per_chip=tps / n_dev,
    )
    log(
        f"[generate] {new_tokens} new tokens in {dt:.2f}s: "
        f"{tps:,.0f} tokens/sec decode ({1000 * dt / max_new_tokens:.1f} "
        f"ms/step at batch {batch_size})"
    )
    result = {
        "metric": "llama_decode_tokens_per_sec_per_chip",
        "value": round(tps / n_dev, 1),
        "unit": "tokens/sec/chip",
        "config": config,
        "params_m": round(n_params / 1e6, 1),
        "batch": batch_size,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new_tokens,
        "max_decode_len": cfg.max_decode_len,
        "devices": n_dev,
    }
    if quantize:
        result["quantize"] = quantize
        result["weight_mb"] = round(weight_bytes / 1e6, 2)
    if kv_quantize:
        result["kv_quantize"] = kv_quantize
    if restored_step is not None:
        result["restored_step"] = restored_step
    if dt_fp is not None:
        result["tokens_per_sec_per_chip_unquantized"] = round(
            new_tokens / dt_fp / n_dev, 1
        )
        result["int8_speedup"] = round(dt_fp / dt, 3)
    return result


def main(argv=None) -> int:
    from ..models.llama import CONFIGS

    p = argparse.ArgumentParser()
    p.add_argument("--config", choices=sorted(CONFIGS), default="tiny")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument(
        "--max-decode-len", type=int, default=None,
        help="static cache length (default prompt+new); larger values "
        "measure serving at a context budget",
    )
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument(
        "--top-k", type=int, default=0,
        help="sample only from the k highest-probability tokens "
        "(0 = off; needs --temperature > 0)",
    )
    p.add_argument(
        "--top-p", type=float, default=1.0,
        help="nucleus sampling: smallest token set reaching this "
        "cumulative probability (1.0 = off; needs --temperature > 0)",
    )
    p.add_argument(
        "--quantize", choices=["int8"], default=None,
        help="weight-only quantization: matmul weights stored int8 in "
        "HBM with per-channel scales, dequant fused into each matmul "
        "(ops/quantize.py) — 4x less weight traffic than f32",
    )
    p.add_argument(
        "--kv-quantize", choices=["int8"], default=None,
        help="store the KV cache int8 with per-(token, head) scales — "
        "halves cache HBM and cache-read traffic; the long-context "
        "serving lever next to --quantize",
    )
    p.add_argument(
        "--init-host", action="store_true",
        help="initialize + quantize params on the host CPU and transfer "
        "only the int8 tree (for models whose full-precision tree "
        "exceeds HBM, e.g. --config 8b); requires --quantize",
    )
    p.add_argument(
        "--compare-unquantized", action="store_true",
        help="also time the full-precision params in the same session "
        "(A/B evidence for the int8 win); requires --quantize",
    )
    p.add_argument(
        "--restore", default=None, metavar="CKPT_DIR",
        help="serve a trained checkpoint: restore params from this "
        "checkpoint directory (a llama_train run's "
        "TPUJOB_CHECKPOINT_DIR) instead of random init",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)

    world = rendezvous.initialize_from_env()
    result = run(
        config=args.config,
        batch_size=args.batch_size,
        prompt_len=args.prompt_len,
        max_new_tokens=args.max_new_tokens,
        max_decode_len=args.max_decode_len,
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        quantize=args.quantize,
        kv_quantize=args.kv_quantize,
        init_host=args.init_host,
        compare_unquantized=args.compare_unquantized,
        restore=args.restore,
        seed=args.seed,
        log=lambda msg: print(msg, flush=True),
    )
    if args.json and world.process_id == 0:
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
