"""Llama-3-family decoder-only transformer, TPU-first.

Reference analog: the Llama-3-8B multi-host PyTorchJob config
(BASELINE.json:10) — the model itself lives in the reference's user
containers; this is a from-scratch flax implementation of the Llama-3
architecture (RMSNorm, rotary embeddings with the rotate-half convention,
SwiGLU MLP, grouped-query attention, untied LM head).

TPU-first choices:
- every parameter carries *logical* axis names (flax spmd metadata); the
  rule table in ``parallel/sharding.py`` maps them onto a dp×fsdp×tp(×sp)
  mesh and XLA inserts the collectives — no hand-written NCCL-style code.
- ``lax.scan`` over layers (one compiled block × n_layers) keeps compile
  time O(1) in depth; optional rematerialization trades FLOPs for HBM.
- bfloat16 activations / float32 params and softmax; static shapes; the
  causal mask is a compile-time constant.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .common import remat_policy

Dtype = Any

# Logical axis vocabulary (see parallel/sharding.py DEFAULT_RULES):
#   "vocab"   → tp      "embed" → fsdp     "heads"/"kv_heads"/"mlp" → tp
#   "batch"   → dp+fsdp "seq"   → sp       "layers" (scan axis) → unsharded
#   "head_dim"/"norm"   → replicated


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff: int = 14_336
    rope_theta: float = 500_000.0
    rms_eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    remat: bool = False  # checkpoint each block (jax.checkpoint under scan)
    # Remat policy when remat=True: "full" (save only block boundaries —
    # minimum HBM, recompute everything in backward) or "dots" (save the
    # outputs of non-batch matmuls via XLA's offloadable-names policy —
    # backward skips recomputing the big GEMMs at the price of holding
    # their outputs; the right trade when HBM has headroom, since the
    # recompute being avoided is exactly the MXU-bound work).
    remat_policy: str = "full"
    # Attention implementation: "dense" (materialized S×S scores), "flash"
    # (pallas blockwise kernel, O(S·D) HBM traffic — ops/flash_attention.py),
    # "ring" (sequence-parallel ring attention over the mesh's ``sp`` axis —
    # parallel/ring.py), "ulysses" (all-to-all seq↔head swap over ``sp`` —
    # parallel/ulysses.py; 2 collectives vs ring's P rotations, full-S
    # scores per local kv head, needs n_kv_heads % sp == 0). ring/ulysses
    # require passing the mesh to the model.
    attn_impl: str = "dense"
    # Loss implementation: "dense" ([B,S,V] logits then optax xent) or
    # "chunked" (fused head+loss over vocab chunks — ops/chunked_xent.py;
    # saves O(B·S·V) HBM, the dominant activation at V=128k).
    xent_impl: str = "dense"
    # Mixture-of-experts: n_experts > 0 replaces the dense SwiGLU MLP with
    # a top-k gated gelu MoE whose experts shard over the mesh's ``ep``
    # axis (parallel/moe.py); 0 = dense.
    n_experts: int = 0
    moe_top_k: int = 2
    # Expert dispatch: "dense" (every device runs its local experts over
    # all tokens — exact, no drops, FLOPs ∝ local experts) or "sparse"
    # (GShard capacity-factor dispatch — FLOPs ∝ top_k·capacity_factor,
    # over-capacity tokens dropped). Dense's cost grows with E, sparse's
    # does not: prefer "sparse" from E >= 16.
    moe_dispatch: str = "dense"
    moe_capacity_factor: float = 1.25
    # Switch-style load-balancing auxiliary loss weight (0 = off). With
    # top-k routing — and capacity-factor sparse dispatch especially,
    # which DROPS over-capacity tokens — an unregularized router
    # collapses onto a few experts; the standard weight is ~1e-2. Sown
    # into the "losses" collection per layer; make_lm_train_step adds
    # weight * mean(per-layer aux) to the objective.
    moe_aux_weight: float = 0.0
    # Autoregressive decoding: ``decode=True`` switches attention to a
    # KV-cache path (flax "cache" collection: cached_key/cached_value of
    # static length ``max_decode_len``, updated in place each step) —
    # prefill writes the whole prompt at once, decode steps append one
    # token. Static shapes throughout: the scores run against the full
    # cache with a position mask, so the decode step is ONE fixed XLA
    # program regardless of how much of the cache is filled.
    decode: bool = False
    max_decode_len: int = 2048
    # KV-cache quantization (decode only): "int8" stores cached_key/
    # cached_value as int8 with per-(token, kv-head) f32 scales
    # (amax/127 over head_dim), quantized at write time, dequantized
    # inside the attention einsums (the convert+scale fuses into the
    # dot's operand read — the cache is a scan CARRY, not a scan input,
    # so no materialization issue arises). Halves cache HBM: the lever
    # that fits long-context 8B serving on one chip next to the int8
    # weights. Independent of ``quantize``.
    kv_quantize: Optional[str] = None
    # Per-row decode offsets (decode only): False keeps the batch-uniform
    # contract (every row at the same position; cache writes are ONE
    # dynamic_update_slice at positions[0,0] — the fastest write and the
    # right one for the single-stream generate loop). True switches the
    # cache write to per-row offsets (positions[:, 0] may differ per row
    # — a batched vmapped update-slice, i.e. a scatter), which is what a
    # continuous-batching serving engine needs: each row of the batch is
    # a DIFFERENT request at a different depth in its own stream. The
    # attention validity mask is per-row in BOTH modes (it reads the
    # full positions array; the uniform case is just the special case
    # where the rows agree).
    decode_per_row: bool = False
    # Multi-token decode inputs (S > 1): "self" = the whole prompt of a
    # FRESH cache (positions [0, S)) — causal self-attention over the
    # incoming tokens alone IS the full attention, so the flash kernel
    # applies and no [B,K,G,S,L] scores materialize. "cache" = a CHUNK
    # of a partially prefilled stream (positions [start, start+S)): the
    # chunk is written to the cache, then attends against the cache's
    # filled prefix (ops/cache_attention.py) with the position-validity
    # mask — intra-chunk causality and the prefix both fall out of
    # col <= row. Memory is at most O(S·L) scores, so chunked prefill
    # picks S (the chunk) to bound it; that bound is the
    # point (one-shot 8B long prompts exceed one program's activation
    # budget).
    prefill_mode: str = "self"
    # Weight-only quantization mode (inference): "int8" makes apply()
    # expect a params tree produced by ``ops.quantize.quantize_tree``
    # (QuantizedTensor leaves — int8 payload + per-channel scales).
    # Dequantization happens INSIDE each consuming module via
    # nn.map_variables — critically, inside the layer-scan body, so the
    # per-layer weights are dequantized AFTER the scan slices them and
    # the convert+scale fuses into each matmul's operand read. A
    # top-level tree dequant instead turns the stacked [L, ...] weights
    # into materialized full-precision scan inputs (measured 2.1x
    # SLOWER than the f32 control at 1b on the chip — the failure mode
    # this field exists to avoid). Plain-array trees still work in this
    # mode (dequant is identity), which is what the same-program
    # quantized-vs-full A/B in workloads/generate.py rides on.
    quantize: Optional[str] = None

    def __post_init__(self):
        if self.quantize not in (None, "int8"):
            # Fail at construction, matching the workload entry point —
            # any truthy value would otherwise silently run the int8
            # dequant hook.
            raise ValueError(
                f"quantize={self.quantize!r} not in (None, 'int8')"
            )
        if self.kv_quantize not in (None, "int8"):
            raise ValueError(
                f"kv_quantize={self.kv_quantize!r} not in (None, 'int8')"
            )
        if self.prefill_mode not in ("self", "cache"):
            raise ValueError(
                f"prefill_mode={self.prefill_mode!r} not in ('self', 'cache')"
            )
        if (self.decode_per_row or self.prefill_mode != "self") and not self.decode:
            raise ValueError(
                "decode_per_row / prefill_mode='cache' require decode=True"
            )
        if self.decode and self.attn_impl in ("ring", "ulysses"):
            # The decode prefill runs plain causal self-attention over
            # the incoming tokens (flash/dense); sequence-parallel
            # schemes don't compose with the KV-cache write layout.
            raise ValueError(
                f"attn_impl={self.attn_impl!r} is not supported with "
                "decode=True (prefill uses flash/dense self-attention)"
            )
        if (
            self.n_experts > 0
            and self.moe_dispatch == "sparse"
            and not self.moe_aux_weight
        ):
            # Capacity-factor dispatch DROPS over-capacity tokens, so an
            # unregularized router collapsing onto a few experts (the
            # moe_aux_weight docstring's failure mode) also silently
            # drops most of the batch — warn at construction, where every
            # entry path (workload flags, library use, import) passes.
            import warnings

            warnings.warn(
                "moe_dispatch='sparse' with moe_aux_weight=0: without the "
                "load-balance loss the router can collapse onto a few "
                "experts and capacity-factor dispatch then drops most "
                "tokens. Set moe_aux_weight~1e-2.",
                stacklevel=2,
            )

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    def serving_model(self):
        """What the serving engine talks to (models/serving.py)."""
        return serving_model(self)


def llama3_8b(**over) -> LlamaConfig:
    """The real Llama-3-8B shape (BASELINE.json:10 target workload).

    Defaults to the pallas flash kernel: at this scale the S×S score
    materialization dominates attention HBM traffic. flash_attention
    zero-pads unaligned shapes to the kernel tiling and masks the padding
    (round 4; no dense fallback cliff). Also defaults to
    the chunked-vocab loss: [B,S,128256] f32 logits would otherwise be the
    single largest activation in the step.
    """
    return LlamaConfig(**{"attn_impl": "flash", "xent_impl": "chunked", **over})


def llama_0_3b(**over) -> LlamaConfig:
    """~0.32B-parameter Llama shape for single-chip benchmarking: the
    largest config that trains comfortably on one v5e chip at long
    sequence lengths. Same architecture and kernel defaults as
    :func:`llama3_8b` (flash attention — head_dim stays 128, the kernel's
    lane width — and chunked-vocab loss).
    """
    return llama3_8b(
        **{
            "vocab_size": 32000,
            "d_model": 1024,
            "n_layers": 16,
            "n_heads": 8,
            "n_kv_heads": 4,
            "head_dim": 128,
            "d_ff": 4096,
            **over,
        }
    )


def llama_1b(**over) -> LlamaConfig:
    """~1.14B-parameter Llama shape: the largest config whose bf16
    params + adafactor state + 'dots'-remat residuals fit one v5e chip
    (batch 2 × seq 4096; batch 4 needs 'full' remat and measures worse).

    Role: the MFU-vs-scale evidence point. The 0.3b config is bounded
    by per-step elementwise/issue floors that amortize with width; this
    config shows how utilization moves with model size on the same chip.
    """
    return llama3_8b(
        **{
            "vocab_size": 32000,
            "d_model": 2048,
            "n_layers": 16,
            "n_heads": 16,
            "n_kv_heads": 8,
            "head_dim": 128,
            "d_ff": 8192,
            **over,
        }
    )


def llama_tiny(**over) -> LlamaConfig:
    """Scaled-down config for tests/dryruns: same architecture, tiny dims."""
    base = dict(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        dtype=jnp.float32,
    )
    base.update(over)
    return LlamaConfig(**base)


class RMSNorm(nn.Module):
    eps: float
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("norm",)),
            (x.shape[-1],),
            self.param_dtype,
        )
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding, rotate-half convention. x: [B,S,H,D], positions: [B,S]."""
    half = x.shape[-1] // 2
    freqs = (theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class Dense(nn.DenseGeneral):
    """``nn.DenseGeneral`` whose kernel may arrive as an int8
    :class:`ops.quantize.QuantizedTensor` (the serving forward hands each
    layer's leaves on as they are held): the product then reads the int8
    payload and the scale follows it (``QuantizedTensor.project``). Every
    projection of a block is one of these, so one rule serves all seven
    weights; a plain kernel is ``nn.DenseGeneral``'s own business (same
    parameter, same init, same product)."""

    def __call__(self, x):
        from ..ops.quantize import QuantizedTensor

        kernel = None if self.is_initializing() else self.get_variable("params", "kernel")
        if isinstance(kernel, QuantizedTensor):
            return kernel.project(x.astype(self.dtype))
        return super().__call__(x)


class Attention(nn.Module):
    """Grouped-query attention with RoPE and a causal mask.

    ``mesh`` is only consulted by the ring implementation (attn_impl="ring"),
    which shards the sequence over the mesh's ``sp`` axis and rotates K/V
    around the ring (parallel/ring.py).
    """

    cfg: LlamaConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x, positions, slot=None):
        cfg = self.cfg
        B, S, _ = x.shape
        H, K, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

        q = Dense(
            (H, D), use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "heads", "head_dim")
            ),
            name="q_proj",
        )(x)
        kv_kernel = nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), ("embed", "kv_heads", "head_dim")
        )
        k = Dense(
            (K, D), use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=kv_kernel, name="k_proj",
        )(x)
        v = Dense(
            (K, D), use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=kv_kernel, name="v_proj",
        )(x)

        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

        # GQA: group q heads over their kv head: [B,S,K,G,D] against [B,S,K,D].
        G = cfg.q_per_kv
        q = q.reshape(B, S, K, G, D)
        if cfg.decode:
            return self._decode_attend(q, k, v, positions, slot)
        if cfg.attn_impl == "ring":
            if self.mesh is None:
                raise ValueError(
                    "attn_impl='ring' needs the mesh: Llama(cfg, mesh=mesh)"
                )
            from ..parallel.ring import ring_self_attention

            out = ring_self_attention(q, k, v, positions, self.mesh)
        elif cfg.attn_impl == "ulysses":
            # All-to-all sequence parallelism (parallel/ulysses.py):
            # attention runs with full S and 1/sp of the kv heads per
            # device — two collectives total vs ring's P rotations.
            if self.mesh is None:
                raise ValueError(
                    "attn_impl='ulysses' needs the mesh: Llama(cfg, mesh=mesh)"
                )
            from ..parallel.ulysses import ulysses_self_attention

            out = ulysses_self_attention(q, k, v, positions, self.mesh)
        else:
            out = self._self_attend(q, k, v)
        out = out.reshape(B, S, H * D)
        out = nn.with_logical_constraint(out, ("batch", "seq", None))

        return self._o_proj(out)

    def _self_attend(self, q, k, v):
        """Causal self-attention over the incoming tokens only (flash or
        dense per ``cfg.attn_impl``): the non-sequence-parallel train
        path, and the decode path's PREFILL (a fresh cache's prompt
        occupies positions [0, S), so attention over the prompt alone is
        the full causal attention — no [B,K,G,S,L] score tensor against
        the whole cache budget, which at S=L=8k would be ~17 GB)."""
        cfg = self.cfg
        B, S, K, G, D = q.shape
        if cfg.attn_impl == "flash":
            # Blockwise pallas kernel; assumes the standard causal layout
            # (positions = arange), which Llama.__call__ defaults to.
            from ..ops.flash_attention import flash_attention

            return flash_attention(
                q.reshape(B, S, K * G, D), k, v, causal=True, mesh=self.mesh
            ).reshape(B, S, K, G, D)
        scores = jnp.einsum(
            "bskgd,btkd->bkgst", q, k, preferred_element_type=jnp.float32
        ) / jnp.sqrt(D).astype(jnp.float32)
        causal = jnp.tril(jnp.ones((S, S), dtype=bool))
        scores = jnp.where(causal, scores, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        return jnp.einsum("bkgst,btkd->bskgd", probs, v)

    def _o_proj(self, out):
        cfg = self.cfg
        return Dense(
            cfg.d_model, axis=-1, use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("heads", "embed")
            ),
            name="o_proj",
        )(out)

    def _decode_attend(self, q, k, v, positions, slot=None):
        """KV-cache attention (prefill AND single-token decode steps).

        Cache: ``cached_key``/``cached_value`` [B, K, max_decode_len, D]
        (heads-major) in the flax "cache" collection, written in place
        at the current positions; scores run q against the cache's filled
        prefix with a position-validity mask (col_pos <= row_pos). The
        prefix is walked in static blocks with a trip count found inside
        the program (``_cache_attend``), so the program's shapes stay
        static however much of the cache is filled.

        CONTRACT (``cfg.decode_per_row=False``): positions must be
        batch-uniform (every row at the same offsets — the standard
        unpadded generate loop); the cache write offset reads row 0.
        With ``decode_per_row=True`` each row writes at its own
        ``positions[b, 0]`` (continuous-batching serving, where every
        row is a different request mid-stream). The attention validity
        mask is per-row in both modes. Because a contract violation is
        silently wrong (not an error), ``TPUJOB_DEBUG_CHECKS=1``
        installs a host-callback assert at the model top level (see
        ``Llama.__call__`` — once per step, not per layer).

        ``slot`` (a traced scalar; the serving engine's prefill chunk):
        the batch is ONE row that lives at row ``slot`` of slabs holding
        every slot. Its keys, values and scales are written at
        ``(slot, :, start:start + S, :)`` of those slabs and its attention
        reads that row's blocks where it cuts them: no row-sized copy
        leaves the slabs or returns to them.
        """
        cfg = self.cfg
        B, S, K, G, D = q.shape
        L = cfg.max_decode_len
        kv8 = cfg.kv_quantize == "int8"
        cache_dtype = jnp.int8 if kv8 else cfg.dtype
        # Heads-major [B, K, L, D] layout: each (b, k) head's [L, D]
        # panel is contiguous for the attention dots. (Measured neutral
        # vs seq-major on its own — XLA picks physical layouts — but it
        # is the natural shape for the per-layer slabs decode_forward
        # threads, and the einsums below read it without relayout.)
        ck = self.variable(
            "cache", "cached_key", jnp.zeros, (B, K, L, D), cache_dtype
        )
        cv = self.variable(
            "cache", "cached_value", jnp.zeros, (B, K, L, D), cache_dtype
        )
        ks = vs = None
        if kv8:
            # Per-(token, kv-head) scales: amax/127 over head_dim — one
            # f32 per D int8 payload bytes (3% overhead at D=128).
            ks = self.variable(
                "cache", "key_scale", jnp.zeros, (B, K, L, 1), jnp.float32
            )
            vs = self.variable(
                "cache", "value_scale", jnp.zeros, (B, K, L, 1), jnp.float32
            )
        if not self.is_initializing():
            # The incoming S tokens sit at contiguous positions starting
            # at positions[:, 0] (prefill: the prompt or a chunk of it;
            # decode: one token at the current index).
            k_in = k.swapaxes(1, 2)  # [B, K, S, D]
            v_in = v.swapaxes(1, 2)
            if kv8:
                from ..ops.quantize import quantize

                with jax.named_scope("kv_quantize"):
                    kq, vq = quantize(k_in, axis=-1), quantize(v_in, axis=-1)
                leaves, vals = (ck, ks, cv, vs), (kq.q, kq.scale, vq.q, vq.scale)
            else:
                leaves, vals = (ck, cv), (k_in.astype(cfg.dtype), v_in.astype(cfg.dtype))
            per_row = cfg.decode_per_row and slot is None
            if per_row and S == 1:
                # A serving decode step: every row's one position at the
                # row's own place, the layer's leaves (an int8 cache's
                # scales with them) in ONE pass over the rows
                # (ops/cache_write.py), not a scatter loop a leaf. As a
                # function of the program: the layers' calls are alike,
                # so the kernel is traced and lowered once a program and
                # not once a layer (0.2 s of start-up each).
                from ..ops.cache_write import write_rows

                with jax.named_scope("cache_write"):
                    new = jax.jit(write_rows)(
                        [leaf.value for leaf in leaves], vals, positions[:, 0]
                    )
            elif per_row:
                # Several positions a row at per-row offsets (a prompt
                # through the per-row model): a batched update-slice,
                # which XLA lowers to a scatter.
                new = [
                    jax.vmap(
                        lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (0, s, 0))
                    )(leaf.value, val, positions[:, 0])
                    for leaf, val in zip(leaves, vals)
                ]
            else:
                # One update-slice: over rows [0, B), or at row ``slot``
                # of slabs that hold every slot.
                at = (0 if slot is None else slot, 0, positions[0, 0], 0)
                new = [
                    jax.lax.dynamic_update_slice(leaf.value, val, at)
                    for leaf, val in zip(leaves, vals)
                ]
            for leaf, value in zip(leaves, new):
                leaf.value = value
        if S > 1 and cfg.prefill_mode == "self":
            # PREFILL (mode "self"): the prompt lands at positions
            # [0, S) of a fresh cache, so causal attention over the
            # incoming tokens alone IS the full attention — run the
            # standard self-attention path (flash when configured:
            # O(S·D) blockwise HBM) after the cache writes above,
            # instead of materializing [B, K, G, S, L] f32 scores
            # against the whole cache budget (~17 GB at S=L=8k — the
            # long-prompt OOM this branch removes). A nonzero prefill
            # start would make this silently wrong, so the
            # TPUJOB_DEBUG_CHECKS callback in ``Llama.__call__``
            # asserts start == 0 for multi-token inputs in this mode;
            # chunked continuations use prefill_mode="cache" below.
            out = self._self_attend(q, k, v)
        else:
            # Single-token decode steps, and (prefill_mode="cache")
            # chunks of a partially prefilled stream: attend against
            # the cache's filled prefix — the chunk's own tokens were
            # written above at their true positions, so intra-chunk
            # causality and the prefix both fall out of the col <= row
            # mask.
            out = self._cache_attend(q, positions, ck, cv, ks, vs, slot)
        out = out.reshape(B, S, K * G * D)
        out = nn.with_logical_constraint(out, ("batch", "seq", None))
        return self._o_proj(out)

    def _cache_attend(self, q, positions, ck, cv, ks, vs, slot=None):
        """q against the cache's filled prefix under a per-(row, token)
        position-validity mask (ops/cache_attention.py: the slab is read
        in blocks found inside the program from ``positions``; an int8
        cache and every chunk up to the one that holds the deepest query's
        position, a decode step over a plain cache each row's up to that
        row's own). Serves
        single-token decode steps (S=1, possibly at per-row depths) and
        chunked-prefill continuations (S>1, prefill_mode="cache"). An
        int8 cache's scales fold into the scores and the probabilities."""
        from ..ops.cache_attention import cache_attention

        scales = (ks.value, vs.value) if ks is not None else ()
        return cache_attention(q, positions, ck.value, cv.value, *scales, slot=slot)


class MLP(nn.Module):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        proj = lambda name: Dense(  # noqa: E731
            cfg.d_ff, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "mlp")
            ),
            name=name,
        )
        h = nn.silu(proj("gate_proj")(x)) * proj("up_proj")(x)
        h = nn.with_logical_constraint(h, ("batch", "seq", "mlp"))
        return Dense(
            cfg.d_model, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("mlp", "embed")
            ),
            name="down_proj",
        )(h)


class MoEMLP(nn.Module):
    """Expert-parallel top-k MoE feed-forward (parallel/moe.py dispatch).

    Experts shard over the mesh's ``ep`` axis via the ``expert`` logical
    annotation; without a mesh (or with ep extent 1) the dense reference
    runs — same math, no shard_map.
    """

    cfg: LlamaConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x):
        from ..parallel.moe import moe_mlp, moe_mlp_reference

        cfg = self.cfg
        E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
        gate = self.param(
            "gate",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", None)
            ),
            (D, E),
            cfg.param_dtype,
        )
        w_in = self.param(
            "w_in",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "embed", "mlp")
            ),
            (E, D, F),
            cfg.param_dtype,
        )
        w_out = self.param(
            "w_out",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "mlp", "embed")
            ),
            (E, F, D),
            cfg.param_dtype,
        )
        # The serving forward hands quantized banks on as they are held.
        from ..ops.quantize import dequantize_tree

        w_in, w_out = dequantize_tree((w_in, w_out), cfg.dtype)
        params = {
            "gate": gate,
            "w_in": w_in.astype(cfg.dtype),
            "w_out": w_out.astype(cfg.dtype),
        }
        x2d = x.reshape(-1, D)
        if cfg.moe_aux_weight > 0:
            from ..parallel.moe import load_balance_loss

            # The router matmul recurs inside the dispatch below; both
            # run outside any shard_map (dispatch tensors are computed
            # replicated), the op is <1% of the expert FFN FLOPs, and
            # XLA CSEs identical-trace repeats — not worth threading
            # precomputed logits through both call paths.
            self.sow(
                "losses",
                "moe_aux",
                load_balance_loss(params, x2d, cfg.moe_top_k),
            )
        ep_live = self.mesh is not None and self.mesh.shape.get("ep", 1) > 1
        if cfg.moe_dispatch not in ("dense", "sparse"):
            raise ValueError(
                f"moe_dispatch={cfg.moe_dispatch!r} not in ('dense', 'sparse')"
            )
        if cfg.moe_dispatch == "sparse":
            from ..parallel.moe import moe_mlp_sparse

            out = moe_mlp_sparse(
                params,
                x2d,
                top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                mesh=self.mesh if ep_live else None,
            )
        elif ep_live:
            out = moe_mlp(params, x2d, mesh=self.mesh, top_k=cfg.moe_top_k)
        else:
            out = moe_mlp_reference(params, x2d, top_k=cfg.moe_top_k)
        return out.reshape(x.shape).astype(x.dtype)


class Block(nn.Module):
    """Pre-norm decoder block; carries (hidden, positions) through scan.
    The second argument is scan's per-layer input, which the model has
    none of; the serving forward, which applies one block at a time,
    hands the cache row its one-row batch lives in (``slot``,
    :meth:`Attention._decode_attend`) through it."""

    cfg: LlamaConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, carry, slot=None):
        x, positions = carry
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        x = x + Attention(self.cfg, self.mesh, name="attn")(
            RMSNorm(self.cfg.rms_eps, name="attn_norm")(x), positions, slot
        )
        if self.cfg.n_experts > 0:
            mlp = MoEMLP(self.cfg, self.mesh, name="moe_mlp")
        else:
            mlp = MLP(self.cfg, name="mlp")
        x = x + mlp(RMSNorm(self.cfg.rms_eps, name="mlp_norm")(x))
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        return (x, positions), None


class Llama(nn.Module):
    """Decoder-only LM: tokens [B,S] int32 → logits [B,S,vocab].

    ``return_hidden=True`` returns the final-norm hidden states [B,S,D]
    instead of applying the LM head — the input to the chunked-vocab loss
    (ops/chunked_xent.py), which fuses head matmul + cross-entropy without
    materializing [B,S,V] logits. The head params exist either way.
    """

    cfg: LlamaConfig
    mesh: Any = None

    @staticmethod
    def head_kernel(params):
        """The LM-head weight [D, V] out of a params tree (unboxed) — the
        model-owned accessor the chunked-loss trainer path uses, so head
        naming stays out of shared infrastructure. Dequantizes an int8
        leaf (the consumer's matmul fuses the convert — plain dots do;
        see LlamaConfig.quantize)."""
        from ..ops.quantize import QuantizedTensor

        w = params["lm_head"]["kernel"]
        if isinstance(w, QuantizedTensor):
            return w.dequantize()
        return w.unbox() if hasattr(w, "unbox") else w

    @nn.compact
    def __call__(self, tokens, positions=None, return_hidden: bool = False):
        import os

        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[-1], dtype=jnp.int32), tokens.shape
            )
        elif cfg.decode and not self.is_initializing():
            # The decode path's KV-cache write offset and validity mask
            # read positions row 0 (_decode_attend contract) — a ragged
            # batch is silently wrong, not an error — and prefill
            # (S > 1) attends over the incoming tokens only, so a
            # nonzero start silently drops context. Debug mode asserts
            # both ONCE at the model top (not per layer); costs one
            # device->host sync per decode step. decode_forward (the
            # serving path, which bypasses this __call__) installs the
            # same check.
            _debug_check_decode_positions(positions, cfg)

        dequant = None
        if cfg.quantize:
            if self.is_initializing():
                raise ValueError(
                    "a quantize-mode model cannot init: init the "
                    "full-precision model and quantize its params with "
                    "ops.quantize.quantize_tree"
                )
            from ..ops.quantize import dequantize_tree as dequant

        embed_cls = (
            nn.map_variables(nn.Embed, "params", dequant) if dequant else nn.Embed
        )
        embed = embed_cls(
            cfg.vocab_size,
            cfg.d_model,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=1.0), ("vocab", "embed")
            ),
            name="embed",
        )
        x = embed(tokens)

        block = Block
        if cfg.remat:
            block = nn.remat(
                Block, prevent_cse=False, policy=remat_policy(cfg)
            )
        if dequant:
            # INSIDE the scan wrapper: the scan slices the stacked int8
            # leaves first, this dequantizes the slice in the body (see
            # LlamaConfig.quantize).
            block = nn.map_variables(block, "params", dequant)
        ScanBlocks = nn.scan(
            block,
            # Per-layer stacking for params, the decode KV cache, and
            # sown aux losses (each gains a leading layer axis).
            variable_axes={"params": 0, "cache": 0, "losses": 0},
            split_rngs={"params": True},
            length=cfg.n_layers,
            metadata_params={nn.PARTITION_NAME: "layers"},
            # Deliberately no unroll knob: an earlier round measured
            # lax.scan unroll=2/4 slower than the rolled scan.
        )
        (x, _), _ = ScanBlocks(cfg, self.mesh, name="layers")((x, positions), None)

        x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
        head_cls = (
            nn.map_variables(nn.DenseGeneral, "params", dequant)
            if dequant
            else nn.DenseGeneral
        )
        lm_head = head_cls(
            cfg.vocab_size, use_bias=False,
            dtype=jnp.float32, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "vocab")
            ),
            name="lm_head",
        )
        if return_hidden:
            if self.is_initializing():
                # Params must exist regardless of the loss path; a 1-token
                # slice keeps the init trace cheap.
                lm_head(x[:, :1])
            return x
        return lm_head(x)

    @nn.nowrap
    def pp_forward(self, params, tokens, *, mesh, microbatches, return_hidden=False):
        """Model-owned pipeline-parallel forward (the hook
        make_lm_train_step calls when the mesh has a pp axis — keeps
        llama param naming out of shared trainer infrastructure, like
        ``head_kernel``). ``nn.nowrap``: this is plain orchestration, not
        a scoped module method — wrapping would make the in-function
        ``Block``/``RMSNorm`` constructions claim ``self`` as parent."""
        return forward_pp(
            self, params, tokens,
            mesh=mesh, microbatches=microbatches, return_hidden=return_hidden,
        )

    @nn.nowrap
    def pp_value_and_grad(self, params, tokens, *, mesh, microbatches):
        """Model-owned 1F1B train gradients (the make_lm_train_step hook
        for ``--pp-schedule 1f1b``); see :func:`train_value_and_grad_pp`."""
        return train_value_and_grad_pp(
            self, params, tokens, mesh=mesh, microbatches=microbatches
        )


def _debug_check_decode_positions(positions, cfg):
    """Install the TPUJOB_DEBUG_CHECKS host assert on decode positions,
    per the config's contract:

    - always: rows are per-row CONTIGUOUS (pos[b, s] = pos[b, 0] + s)
      and the last write lands inside the cache (pos < max_decode_len —
      dynamic_update_slice would silently CLAMP an overflow and corrupt
      the newest cache rows).
    - ``decode_per_row=False``: batch-uniform (the cache write offset
      reads row 0).
    - ``prefill_mode="self"``: multi-token inputs start at position 0
      (self-attention prefill would silently drop earlier context at a
      nonzero start; chunked continuations need prefill_mode="cache").

    No-op unless the env var is set."""
    import os

    if os.environ.get("TPUJOB_DEBUG_CHECKS", "").lower() in (
        "", "0", "false", "no",
    ):
        return
    per_row, prefill_mode, L = (
        cfg.decode_per_row, cfg.prefill_mode, cfg.max_decode_len,
    )

    def _assert_valid(pos):
        import numpy as np

        S = pos.shape[-1]
        if not (pos == pos[:, :1] + np.arange(S)).all():
            raise ValueError(
                f"decode positions must be contiguous per row; got {pos}"
            )
        if not per_row and not (pos == pos[0:1]).all():
            raise ValueError(
                "decode positions must be batch-uniform (unpadded "
                f"equal-length batch); got rows {pos}. Bucket ragged "
                "prompts to equal length, generate row-by-row, or build "
                "the model with decode_per_row=True (serving engine)."
            )
        if pos.max() >= L:
            raise ValueError(
                f"decode position {pos.max()} >= max_decode_len {L}: "
                "the cache write would clamp and corrupt the rollout"
            )
        if prefill_mode == "self" and S > 1 and (pos[:, 0] != 0).any():
            raise ValueError(
                "multi-token decode input (prefill) must start at "
                f"position 0, got starts {pos[:, 0]}: prefill_mode="
                "'self' attends over the incoming tokens only. Chunked "
                "prefill needs prefill_mode='cache'."
            )

    jax.debug.callback(_assert_valid, positions)


def init_decode_cache(cfg: LlamaConfig, batch: int):
    """Zero KV cache for :func:`decode_forward`: a flat per-layer dict
    (``layer_0`` .. ``layer_{n-1}``), each holding the slab the block's
    attention declares — NOT the flax-scan stacked form. The flat form
    is the point: per-layer slabs flow as plain scan-carry leaves, so a
    decode step's only cache writes are one token-slice
    dynamic_update_slice per layer, updated in place."""
    B, L, K, D = batch, cfg.max_decode_len, cfg.n_kv_heads, cfg.head_dim
    kv8 = cfg.kv_quantize == "int8"

    def slab():
        # Fresh arrays per layer: shared buffers would alias when the
        # caller donates the cache into the jitted generate.
        s = {
            "cached_key": jnp.zeros(
                (B, K, L, D), jnp.int8 if kv8 else cfg.dtype
            ),
            "cached_value": jnp.zeros(
                (B, K, L, D), jnp.int8 if kv8 else cfg.dtype
            ),
        }
        if kv8:
            s["key_scale"] = jnp.zeros((B, K, L, 1), jnp.float32)
            s["value_scale"] = jnp.zeros((B, K, L, 1), jnp.float32)
        return s

    return {f"layer_{i}": {"attn": slab()} for i in range(cfg.n_layers)}


def decode_forward(
    model: "Llama",
    params,
    cache,
    tokens,
    positions=None,
    *,
    return_hidden: bool = True,
    slot=None,
):
    """The SERVING forward: numerically identical to
    ``Llama(decode=True).apply`` (pinned by test), but with the layer
    loop UNROLLED and the KV cache as an explicit argument/return
    (:func:`init_decode_cache` layout) instead of a flax-scan-lifted
    collection.

    Why this exists: under ``nn.scan(variable_axes={"cache": 0})`` every
    decode step dynamic-slices each layer's whole slab out of the
    stacked cache, rewrites it wholesale, and copies the stack — an
    xplane profile at 1b/b8/L=4096 showed most of the step going to
    exactly that (copies and dynamic-slice/update-slice fusions).
    Here each layer's slab is a plain carry leaf: the step reads it once
    (fused into the attention einsums) and writes ONE token slice in
    place. Quantized (``cfg.quantize``) trees reach each layer's modules
    as they are held: a projection multiplies by the int8 payload and
    scales the result (:class:`Dense`), so no dequantised copy of a
    weight is ever an array of its own — python-unrolled, so there is no
    scan-input materialization hazard and no map_variables hook is needed.

    ``slot`` (a traced scalar): ``tokens`` is one row, whose cache is row
    ``slot`` of ``cache``'s slabs; it is written and read in place
    (:meth:`Attention._decode_attend`). Without it row ``b`` of the batch
    owns row ``b`` of the cache.

    Returns ``(hidden_or_logits, new_cache)``.
    """
    from ..ops.quantize import QuantizedTensor, dequantize_tree

    cfg = model.cfg
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[-1], dtype=jnp.int32), tokens.shape
        )
    else:
        # Same TPUJOB_DEBUG_CHECKS contract assert as Llama.__call__
        # (this path bypasses it); the checked contract follows the
        # config: batch-uniform unless decode_per_row, start-0 prefill
        # unless prefill_mode="cache".
        _debug_check_decode_positions(positions, model.cfg)
    p = nn.meta.unbox(params)

    table = p["embed"]["embedding"]
    with jax.named_scope("embed"):
        if isinstance(table, QuantizedTensor):
            # Gather rows first, dequantize the gathered rows (per-row
            # scales) — never the whole table.
            x = (
                table.q[tokens].astype(jnp.float32) * table.scale[tokens]
            ).astype(cfg.dtype)
        else:
            x = table.astype(cfg.dtype)[tokens]

    block = Block(cfg, model.mesh)

    # ONE function for every layer: the layers have one shape, so the second
    # to the last call find the first one's trace and the program holds the
    # layer once, called ``n_layers`` times (the compiler inlines the calls:
    # what runs is the unrolled loop's program). Traced a layer at a time, 24
    # layers were ~1.4 s of every program's start (PERF.md section 6, PR 47).
    @jax.jit
    def layer(lp, layer_cache, x, positions, slot):
        with nn.logical_axis_rules(()):
            ((x, _pos), _), upd = block.apply(
                {"params": lp, "cache": layer_cache}, (x, positions), slot, mutable=["cache"]
            )
        return x, upd["cache"]

    layers = p["layers"]
    new_cache = {}
    for i in range(cfg.n_layers):
        # A layer's own tree where the parameters are held per layer
        # (:func:`per_layer_params`, the serving arrangement); else a static
        # slice of the scan-stacked leaves (QuantizedTensor is a pytree
        # node, so its q/scale fields are sliced like any other leaf).
        lp = layers[i] if isinstance(layers, list) else jax.tree.map(lambda a: a[i], layers)
        x, new_cache[f"layer_{i}"] = layer(lp, cache[f"layer_{i}"], x, positions, slot)

    x = RMSNorm(cfg.rms_eps).apply(
        {"params": dequantize_tree(p["final_norm"])}, x
    )
    if return_hidden:
        return x, new_cache
    with jax.named_scope("head"):
        w = Llama.head_kernel(p)
        logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
    return logits, new_cache


def per_layer_params(params):
    """``params`` with its scan-stacked ``layers`` (every leaf leading
    with the layer axis: what ``Llama.init`` makes and the trainer saves)
    as a list of per-layer trees, which is how the serving forward reads
    them fastest: sliced out of a stacked parameter inside a program, a
    layer's int8 q/k/v kernels were copied to an array of their own at
    every prefill chunk and every layer's weights read slower at every
    decode step (PERF.md section 6, PR 31: 4.31 -> 3.68 ms a chunk, 8.23
    -> 7.13 ms a step on the v5e). Plain indexing: views of a host
    checkpoint, slices inside the program that makes or quantises the
    weights (where it costs nothing: that program writes each layer's
    leaves instead of the stack). A tree already held per layer passes
    through."""
    layers = params.get("layers")
    if not layers or isinstance(layers, list):  # nothing to arrange (a caller's shape check says what is missing)
        return params
    n = jax.tree.leaves(layers)[0].shape[0]
    return {**params, "layers": [jax.tree.map(lambda a: a[i], layers) for i in range(n)]}


def serving_model(cfg: LlamaConfig):
    """This family behind ``models.serving.ServingModel``: a per-row decode
    model and a chunked-prefill (``prefill_mode="cache"``) model over one
    :func:`init_decode_cache`, both through :func:`decode_forward`; the
    seeded init is the training model's (float32, the whole tree in one
    program: ``workloads.generate.load_params`` quantises or commits it),
    with the layers a tree each, as ``arrange`` makes a checkpoint's."""
    from ..ops.cache_attention import reads_per_row
    from .serving import ServingModel

    if not cfg.decode:
        raise ValueError("serving needs a decode=True config")
    cfg = dataclasses.replace(cfg, decode_per_row=False, prefill_mode="self")
    decode_model = Llama(dataclasses.replace(cfg, decode_per_row=True))
    prefill_model = Llama(dataclasses.replace(cfg, prefill_mode="cache"))
    train_cfg = dataclasses.replace(cfg, decode=False, quantize=None)

    def init_params(key):
        # Looked up at call time: a caller that brings its own seeded
        # leaves (the benchmark) wraps ``Llama.init``. The one program
        # writes the layers a tree each (:func:`per_layer_params`).
        return jax.jit(
            lambda k: per_layer_params(
                nn.meta.unbox(Llama(train_cfg).init(k, jnp.zeros((1, 8), jnp.int32))["params"])
            )
        )(key)

    def prefill(params, cache, slot, tokens, positions, n_real=None):
        # ``n_real`` changes nothing here: a pad's keys and values are
        # masked by position or overwritten (serving/engine.py).
        hidden, cache = decode_forward(
            prefill_model, params, cache, tokens, positions, return_hidden=True, slot=slot
        )
        return hidden, cache, {}

    def decode(params, cache, tok, pos):
        logits, cache = decode_forward(
            decode_model, params, cache, tok, pos, return_hidden=False
        )
        return logits[:, -1], cache, {}

    def logits(params, hidden):
        w = Llama.head_kernel(params)
        return hidden.astype(jnp.float32) @ w.astype(jnp.float32)

    return ServingModel(
        cfg=cfg,
        init_params=init_params,
        init_cache=lambda slots, chunk: init_decode_cache(cfg, slots),
        prefill=prefill,
        decode=decode,
        logits=logits,
        decode_reads_per_row=reads_per_row(quantized=cfg.kv_quantize == "int8"),
        prefill_any_width=True,
        arrange=per_layer_params,
    )


def forward_pp(
    model: "Llama",
    params,
    tokens,
    *,
    mesh,
    microbatches: int,
    return_hidden: bool = False,
):
    """Pipeline-parallel forward: the layer stack runs through
    ``parallel.pipeline.pipeline_apply`` over the mesh's ``pp`` axis,
    numerically identical to ``model.apply`` (same params, same order).

    The scan-stacked layer params (leading axis n_layers) regroup into
    P stages of n_layers/P consecutive layers; embed / final norm / LM
    head run outside the pipeline under the surrounding pjit (their
    FLOPs are a sliver of the stack's, and keeping them SPMD avoids
    first/last-stage special cases). ``cfg.remat`` applies per layer
    inside each stage. Composes with dp/fsdp on the same mesh —
    pipeline_apply takes manual control of pp only.

    Constraints: ``cfg.n_layers % pp == 0``; ring attention (sp) cannot
    nest inside the pp pipeline.
    """
    from ..parallel.pipeline import pipeline_apply

    cfg = model.cfg
    p, stage_params, stage = _pp_parts(model, params, mesh)

    # Embedding lookup, matching nn.Embed(dtype=cfg.dtype) semantics
    # (table cast to the compute dtype, then take).
    x = p["embed"]["embedding"].astype(cfg.dtype)[tokens]

    x = pipeline_apply(
        stage, stage_params, x, mesh=mesh, microbatches=microbatches
    )

    x = RMSNorm(cfg.rms_eps, name="final_norm").apply(
        {"params": p["final_norm"]}, x
    )
    if return_hidden:
        return x
    # DenseGeneral(dtype=float32) semantics: promote input and kernel.
    w = p["lm_head"]["kernel"]
    return x.astype(jnp.float32) @ w.astype(jnp.float32)


def _pp_parts(model: "Llama", params, mesh):
    """The shared pp decomposition behind forward_pp and
    train_value_and_grad_pp: ``(unboxed_params, stage_params, stage_fn)``
    — the scan-stacked layer params (leading axis n_layers) regrouped
    into P stages of n_layers/P consecutive layers, and the per-stage
    computation over them."""
    import jax

    cfg = model.cfg
    n_stages = mesh.shape["pp"]
    if cfg.quantize:
        raise ValueError(
            "quantize-mode params (inference) cannot run the pp pipeline"
        )
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp={n_stages}"
        )
    if cfg.attn_impl in ("ring", "ulysses"):
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r} cannot run inside the pp pipeline"
        )
    p = nn.meta.unbox(params)
    stage_params = jax.tree.map(
        lambda l: l.reshape((n_stages, cfg.n_layers // n_stages) + l.shape[1:]),
        p["layers"],
    )
    # Blocks inside the pipeline get mesh=None: pp is already manual in
    # pipeline_apply, and the remaining axes (dp/fsdp) are compiler-
    # propagated — the block needs no mesh consultation (ring is the one
    # mesh consumer, rejected above; flash runs unwrapped).
    block = Block(cfg, None)

    def stage(sp, act):
        pos = jnp.broadcast_to(
            jnp.arange(act.shape[1], dtype=jnp.int32), act.shape[:2]
        )

        def layer(carry, lp):
            # Logical-axis rules off inside the pipeline: pp is manual
            # here, so flax's constraint/unbox machinery would try to
            # bind logical names against a Manual-axis mesh and reject;
            # the remaining axes (dp/fsdp) propagate through shard_map's
            # auto mode without annotations.
            with nn.logical_axis_rules(()):
                out, _ = block.apply({"params": lp}, carry, None)
            return out, None

        if cfg.remat:
            layer = jax.checkpoint(
                layer, prevent_cse=False, policy=remat_policy(cfg)
            )
        (act_out, _pos), _ = jax.lax.scan(layer, (act, pos), sp)
        return act_out

    return p, stage_params, stage


def train_value_and_grad_pp(
    model: "Llama",
    params,
    tokens,
    *,
    mesh,
    microbatches: int,
):
    """1F1B fused train gradients for the llama stack: returns
    ``(loss, grads)`` with grads matching the (boxed) params tree —
    numerically equal to ``jax.value_and_grad`` over the GPipe forward,
    but with per-stage activation residency bounded by the schedule
    depth O(P·mb) instead of O(M·mb)
    (parallel/pipeline.pipeline_value_and_grad; backward='stored'
    residual stashing keeps compute at GPipe parity).

    The embed lookup runs outside the pipeline (its input-cotangent
    stream dx comes back from the pipeline's backward); the final norm +
    LM head + next-token loss run INSIDE as a VOCAB-PARALLEL loss tail
    (``sharded_loss=True``) whenever pp > 1 and the vocab divides: the
    head kernel is chunked ``[P, d, V/P]`` over the pp axis, every stage
    computes online-softmax partial stats for its columns
    (ops.chunked_xent.chunked_vocab_stats — ``cfg.xent_impl='chunked'``
    streams [N, 8192] sub-chunks, 'dense' takes the local V/P in one
    pass), and the per-token log-sum-exp + target logit combine with one
    pmax + two psums. This is the round-4 fix for the P-fold loss-tail
    duplication: the tail costs ~1/P per stage instead of 1× per stage.

    Degenerate/fallback cases keep the REPLICATED tail (the pre-round-4
    behavior, correct at any vocab): pp=1 (nothing to shard), or a vocab
    that does not divide the pp extent (warns — the tail then duplicates
    P-fold, so prefer a divisible vocab/pp pairing).

    MoE aux losses are not supported on pp meshes (same restriction as
    the GPipe path — flax sow collections don't thread the pipeline).
    """
    import jax

    from ..ops.chunked_xent import chunked_vocab_stats
    from ..parallel.pipeline import pipeline_value_and_grad

    cfg = model.cfg
    if getattr(cfg, "moe_aux_weight", 0.0):
        raise ValueError(
            "moe_aux_weight is not supported on a pp mesh (the pipeline "
            "path bypasses flax sow collections)"
        )
    p, stage_params, stage = _pp_parts(model, params, mesh)
    n_stages = mesh.shape["pp"]
    V = cfg.vocab_size
    sharded = n_stages > 1 and V % n_stages == 0
    if n_stages > 1 and not sharded:
        import warnings

        warnings.warn(
            f"vocab_size={V} does not divide pp={n_stages}: the pipeline "
            "loss tail cannot be vocab-parallel and will run replicated "
            f"on every stage ({n_stages}x duplicated head FLOPs). Prefer "
            "a vocab/pp pairing that divides.",
            stacklevel=2,
        )

    x, embed_vjp = jax.vjp(
        lambda table: table.astype(cfg.dtype)[tokens], p["embed"]["embedding"]
    )
    w_full = p["lm_head"]["kernel"]  # [d, V]

    def norm_hidden(scale_params, y_mb):
        h = RMSNorm(cfg.rms_eps).apply({"params": scale_params}, y_mb)
        return h[:, :-1].reshape(-1, h.shape[-1])

    if sharded:
        Vp = V // n_stages
        lp = {
            # Stage s owns vocab columns [s*Vp, (s+1)*Vp).
            "w": jnp.moveaxis(
                w_full.reshape(w_full.shape[0], n_stages, Vp), 1, 0
            ),
            # The norm scale is tiny: stack P copies; total grad = sum of
            # the per-stage partials (each stage's chunk loss consumed
            # its copy).
            "final_norm": jax.tree.map(
                lambda l: jnp.broadcast_to(l, (n_stages,) + l.shape),
                p["final_norm"],
            ),
        }

        def loss_fn(lp_, y_mb, tok_mb):
            # Vocab-parallel next-token xent: per-stage online-softmax
            # partials + collective log-sum-exp. Equals optax integer-
            # label xent on the assembled logits. (m carries no tangent,
            # so pmax — which has no differentiation rule — is skipped
            # by AD.)
            hh = norm_hidden(lp_["final_norm"], y_mb)
            labels = tok_mb[:, 1:].reshape(-1)
            off = jax.lax.axis_index("pp") * Vp
            chunk = 8192 if cfg.xent_impl == "chunked" else Vp
            m, s, lab = chunked_vocab_stats(
                hh, lp_["w"], labels, chunk=chunk, col_offset=off
            )
            m_g = jax.lax.pmax(m, "pp")
            se = jax.lax.psum(s * jnp.exp(m - m_g), "pp")
            tgt = jax.lax.psum(lab, "pp")
            return (m_g + jnp.log(se) - tgt).mean()

        def reassemble(d_lp):
            return {
                "final_norm": jax.tree.map(
                    lambda g: g.sum(0), d_lp["final_norm"]
                ),
                "lm_head": {
                    "kernel": jnp.moveaxis(d_lp["w"], 0, 1).reshape(
                        w_full.shape
                    )
                },
            }

    else:
        import optax

        lp = {"final_norm": p["final_norm"], "lm_head": p["lm_head"]}

        def loss_fn(lp_, y_mb, tok_mb):
            hh = norm_hidden(lp_["final_norm"], y_mb)
            w = lp_["lm_head"]["kernel"]
            labels = tok_mb[:, 1:].reshape(-1)
            if cfg.xent_impl == "chunked":
                from ..ops.chunked_xent import chunked_softmax_xent

                return chunked_softmax_xent(hh, w, labels).mean()
            logits = hh.astype(jnp.float32) @ w.astype(jnp.float32)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            ).mean()

        def reassemble(d_lp):
            return {
                "final_norm": d_lp["final_norm"],
                "lm_head": d_lp["lm_head"],
            }

    loss, (d_stage, d_lp, dx) = pipeline_value_and_grad(
        stage, loss_fn, stage_params, lp, x, tokens,
        mesh=mesh, microbatches=microbatches, schedule="1f1b",
        sharded_loss=sharded,
        # Megatron-style residual stashing: backward reuses the forward's
        # policy-saved residuals (compute parity with GPipe) instead of
        # re-running each stage from its saved input. The transformer
        # stage's residuals are shape-separable (activations carry the
        # microbatch dim; weights/tables don't), which is exactly the
        # contract backward='stored' needs.
        backward="stored",
    )
    (d_embed,) = embed_vjp(dx)
    grads_unboxed = {
        "embed": {"embedding": d_embed},
        "layers": jax.tree.map(
            lambda g, ref: g.reshape(ref.shape), d_stage, p["layers"]
        ),
        **reassemble(d_lp),
    }
    # Re-box to the params tree's flax metadata so the optimizer sees the
    # exact params structure (Partitioned leaves and all).
    return loss, jax.tree.map(
        lambda box, g: (
            box.replace_boxed(g)
            if isinstance(box, nn.meta.Partitioned)
            else g
        ),
        params,
        grads_unboxed,
        is_leaf=lambda v: isinstance(v, nn.meta.Partitioned),
    )


# Preset name -> the function above that builds its config. A caller may add
# one (the benchmark's ``bench``); ``workloads.llama_train`` re-exports this
# same dict, and ``models.serving.families`` reads it at call time.
CONFIGS = {
    "8b": "llama3_8b",
    "1b": "llama_1b",
    "0.3b": "llama_0_3b",
    "tiny": "llama_tiny",
}
