"""A layer-pattern decoder for serving (the ``mimo_v2`` family): the model
is an explicit list of layers, each an attention kind and a feed-forward
kind, with per-layer parameter trees and a per-layer cache of its own kind.

Reference analog: none (the reference is a training operator). Where
``models/llama.py`` scans ONE block over stacked parameters, here the
layers differ in shape — full attention (few key/value heads, every
position kept) beside window attention (more key/value heads, a ring of
the last positions, a learned per-head sink logit in the softmax's
denominator), a dense SwiGLU layer beside sigmoid-routed experts of which
this chip holds a share (parallel/moe.py) — so nothing is stacked and the
layer loop is python-unrolled, as ``models.llama.decode_forward`` unrolls
its own.

The layer (pre-norm residual, RMSNorm, no biases, untied head):

- attention: ``q [H, dqk]``, ``k [Hk, dqk]``, ``v [Hk, dv]`` with ``Hk``
  by kind; rotary embedding (rotate-half) on the first ``rotary_dim``
  components of q and k with the kind's own theta, the rest pass through;
  ``v`` scaled by ``value_scale``; scores ``q.k / sqrt(dqk)``; key ``j``
  visible to query ``i`` iff ``j <= i`` and, in a window layer,
  ``i - j < window``; a window layer's softmax has the head's sink logit
  in its denominator (the sink takes mass and adds no value);
- feed-forward: SwiGLU of width ``d_ff``, or ``moe_swiglu_held``.

TPU-first shape: everything static. A full layer's cache is a
``[slots, Hk, max_decode_len, d]`` slab of which attention reads the filled
prefix, in static blocks (ops/cache_attention.py: a decode step each row's
up to that row's own position, a chunk up to its last),
under a position mask; a window layer's is a ring of ``window + chunk`` positions
addressed by ``position % ring`` that also records WHICH position each entry holds, so
an entry is live iff its recorded position passes the same mask: a slot
taken by a new request never sees the last one's keys (their recorded
positions lie ahead of every query until they are overwritten), and a
ring that wrapped many times equals the masked full-length computation.
Serving only: no training path, no weight or cache quantisation.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import layer_list
from .layer_list import logits, rms_norm, write_positions

Dtype = Any

FULL, WINDOW = "full", "window"
DENSE, MOE = "dense", "moe"


@dataclasses.dataclass(frozen=True)
class MiMoV2Config:
    vocab_size: int = 152_576
    d_model: int = 4096
    n_heads: int = 64
    qk_head_dim: int = 192
    v_head_dim: int = 128
    rotary_dim: int = 64  # partial_rotary_factor 0.334 x 192, rounded down to even
    n_kv_heads_full: int = 4
    n_kv_heads_window: int = 8
    rope_theta: float = 1e7
    window_rope_theta: float = 1e4
    window: int = 128
    value_scale: float = 0.707
    d_ff: int = 16_384  # the dense layer's width
    d_expert: int = 2048
    router_width: int = 256  # experts of the whole layer: what the router scores
    experts_held: tuple[int, int] = (0, 256)  # (first id, count) whose weights live here
    top_k: int = 8
    # One (attention kind, feed-forward kind) per layer.
    layers: tuple[tuple[str, str], ...] = ((FULL, DENSE),)
    rms_eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    # The serving dtype: weights are MADE in it, a layer at a time (norm
    # scales, sink logits and the selection bias stay float32).
    param_dtype: Dtype = jnp.bfloat16
    decode: bool = False
    max_decode_len: int = 4096

    def __post_init__(self):
        for kind in self.layers:
            if kind[0] not in (FULL, WINDOW) or kind[1] not in (DENSE, MOE):
                raise ValueError(f"layer kind {kind!r} is not (full|window, dense|moe)")
        layer_list.check_experts_held(self)
        if self.rotary_dim % 2 or self.rotary_dim > self.qk_head_dim:
            raise ValueError(f"rotary_dim={self.rotary_dim} must be even and <= qk_head_dim")

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    def n_kv_heads(self, attn_kind: str) -> int:
        return self.n_kv_heads_full if attn_kind == FULL else self.n_kv_heads_window

    def serving_model(self):
        """What the serving engine talks to (models/serving.py)."""
        from ..ops.cache_attention import reads_per_row
        from .serving import ServingModel

        if not self.decode:
            raise ValueError("serving needs a decode=True config")
        return ServingModel(
            cfg=self,
            # init_params looks init_layer / init_outer up when called.
            init_params=functools.partial(init_params, self),
            init_cache=functools.partial(init_cache, self),
            prefill=functools.partial(_prefill, self),
            decode=functools.partial(_decode, self),
            logits=logits,
            decode_reads_per_row=reads_per_row(),
            counts=layer_list.zero_moe_counts(self),
            gauges=cache_bytes,
            derive=functools.partial(layer_list.derived_moe_stats, self),
        )


def layer_pattern(hybrid_layer_pattern, moe_layer_freq) -> tuple:
    """The published per-layer lists (0 = full / dense, 1 = window / experts)
    as this module's layer kinds."""
    return tuple(
        (WINDOW if w else FULL, MOE if m else DENSE)
        for w, m in zip(hybrid_layer_pattern, moe_layer_freq, strict=True)
    )


def make_config(base: dict, over: dict) -> MiMoV2Config:
    """A preset with the server's overrides; this family serves plain
    bfloat16 weights and cache."""
    over = dict(over)
    for knob in ("quantize", "kv_quantize"):
        if over.pop(knob, None):
            raise ValueError(f"the mimo_v2 family serves unquantised weights and cache: no {knob}")
    return MiMoV2Config(**{**base, **over})


def mimo_v2_5_ep16(**over) -> MiMoV2Config:
    """MiMo-V2.5's published widths as ONE of 16 chips that share each
    layer by expert parallelism: experts 0-15 of the 256 of every expert
    layer here; attention, router, embedding and head whole. Layers 0-6 of
    the published 48 (full + dense, four window, full, window: the leading
    dense layer and one whole period of the pattern), the rest lying on
    further chips as pipeline stages."""
    window, moe = (0, 1, 1, 1, 1, 0, 1), (0, 1, 1, 1, 1, 1, 1)
    return make_config(
        {"experts_held": (0, 16), "layers": layer_pattern(window, moe)}, over
    )


def mimo_v2_tiny(**over) -> MiMoV2Config:
    """The same structure at test size: every mechanism present (two head
    sizes, two key/value head counts, partial rotary, a window shorter than
    a prompt, sinks, 16 experts top-4 of which 4 are held)."""
    window, moe = (0, 1, 1, 1, 1, 0, 1), (0, 1, 1, 1, 1, 1, 1)
    base = dict(
        vocab_size=256, d_model=64, n_heads=8, qk_head_dim=24, v_head_dim=16,
        rotary_dim=8, n_kv_heads_full=2, n_kv_heads_window=4, window=8,
        d_ff=128, d_expert=32, router_width=16, experts_held=(0, 4), top_k=4,
        layers=layer_pattern(window, moe), dtype=jnp.float32,
        param_dtype=jnp.float32, max_decode_len=128,
    )
    return make_config(base, over)


# Presets by the name a job's ``--config`` gives (models/serving.py).
CONFIGS = {
    "mimo-v2.5-ep16": "mimo_v2_5_ep16",
    "mimo-tiny": "mimo_v2_tiny",
}


# ---- parameters: made in the serving dtype, a layer at a time ----


def layer_shapes(cfg: MiMoV2Config, kind: tuple[str, str]) -> dict:
    """``path -> (shape, fan_in, dtype)`` of one layer's leaves. ``fan_in``
    None = ones (a norm scale); 0 = zeros (a sink logit, the selection
    bias: what a freshly made model has)."""
    attn_kind, ff_kind = kind
    D, H, dqk, dv = cfg.d_model, cfg.n_heads, cfg.qk_head_dim, cfg.v_head_dim
    Hk, w, f32 = cfg.n_kv_heads(attn_kind), cfg.param_dtype, jnp.float32
    out = {
        ("attn", "q_proj"): ((D, H, dqk), D, w),
        ("attn", "k_proj"): ((D, Hk, dqk), D, w),
        ("attn", "v_proj"): ((D, Hk, dv), D, w),
        ("attn", "o_proj"): ((H * dv, D), H * dv, w),
        ("attn_norm", "scale"): ((D,), None, f32),
        ("mlp_norm", "scale"): ((D,), None, f32),
    }
    if attn_kind == WINDOW:
        out[("attn", "sink")] = ((H,), 0, f32)
    if ff_kind == DENSE:
        F = cfg.d_ff
        out.update({
            ("mlp", "gate_proj"): ((D, F), D, w),
            ("mlp", "up_proj"): ((D, F), D, w),
            ("mlp", "down_proj"): ((F, D), F, w),
        })
    else:
        n, F, E = cfg.experts_held[1], cfg.d_expert, cfg.router_width
        out.update({
            ("moe", "router"): ((D, E), D, w),
            ("moe", "e_bias"): ((E,), 0, f32),
            ("moe", "w_gate"): ((n, D, F), D, w),
            ("moe", "w_up"): ((n, D, F), D, w),
            ("moe", "w_down"): ((n, F, D), F, w),
        })
    return out


def init_layer(cfg: MiMoV2Config, kind: tuple[str, str], key, layer) -> dict:
    """Layer ``layer``'s leaves (``layer`` may be traced: layers of one kind
    share a compiled program)."""
    return layer_list.draw(jax.random.fold_in(key, layer), layer_shapes(cfg, kind))


def init_outer(cfg: MiMoV2Config, key) -> dict:
    return layer_list.draw(jax.random.fold_in(key, 1 << 20), layer_list.outer_shapes(cfg))


def init_params(cfg: MiMoV2Config, key) -> dict:
    """The serving tree, a layer at a time (``layer_list.init_params``).
    ``init_layer`` and ``init_outer`` are looked up at call time, so a
    caller that brings its own seeded leaves (the benchmark) replaces those
    two."""
    return layer_list.init_params(cfg, key, cfg.layers, init_outer, init_layer)


# ---- the cache: one state per layer, of the layer's own kind ----


def ring_len(cfg: MiMoV2Config, chunk: int) -> int:
    """Positions a window layer keeps: a chunk's queries see back
    ``window - 1`` positions before the chunk's first, and the chunk's own
    ``chunk`` positions are written before it attends."""
    return min(cfg.max_decode_len, cfg.window + chunk)


def init_cache(cfg: MiMoV2Config, slots: int, chunk: int) -> dict:
    """``layer_i -> {k, v}`` for a full layer (``max_decode_len``
    positions), ``{k, v, pos}`` for a window layer (a ring; ``pos`` the
    position each entry holds, -1 = none yet). Every leaf leads with the
    slot axis."""
    L, R = cfg.max_decode_len, ring_len(cfg, chunk)
    cache = {}
    for i, (attn_kind, _) in enumerate(cfg.layers):
        Hk, T = cfg.n_kv_heads(attn_kind), (L if attn_kind == FULL else R)
        state = {
            "k": jnp.zeros((slots, Hk, T, cfg.qk_head_dim), cfg.dtype),
            "v": jnp.zeros((slots, Hk, T, cfg.v_head_dim), cfg.dtype),
        }
        if attn_kind == WINDOW:
            state["pos"] = jnp.full((slots, R), -1, jnp.int32)
        cache[f"layer_{i}"] = state
    return cache


def cache_bytes(cache: dict) -> dict:
    """The two gauges: bytes held by the full layers' slabs and by the
    window layers' rings."""
    size = lambda state: sum(a.size * a.dtype.itemsize for a in state.values())
    return {
        "cache_full_bytes": sum(size(s) for s in cache.values() if "pos" not in s),
        "cache_window_bytes": sum(size(s) for s in cache.values() if "pos" in s),
    }


# ---- the forward ----


def partial_rope(x, positions, theta: float, rotary_dim: int):
    """Rotate-half rotary embedding on the first ``rotary_dim`` components
    of x ``[B, S, heads, d]``; the rest pass through."""
    half = rotary_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rotary_dim].astype(jnp.float32)
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rot.astype(x.dtype), x[..., rotary_dim:]], axis=-1)


def attention(cfg: MiMoV2Config, attn_kind: str, w: dict, state: dict, x, positions):
    """One layer's attention for ``x [B, S, D]`` at ``positions [B, S]``
    (contiguous in a row) through its cache ``state``: the incoming keys
    and values are written first, then the queries attend a full layer's
    filled prefix (ops/cache_attention.py) or a window layer's whole ring
    under the position mask. Returns (out [B, S, D], new state)."""
    B, S, _ = x.shape
    H, Hk = cfg.n_heads, cfg.n_kv_heads(attn_kind)
    theta = cfg.rope_theta if attn_kind == FULL else cfg.window_rope_theta
    q = jnp.einsum("bsd,dhe->bshe", x, w["q_proj"])
    k = jnp.einsum("bsd,dke->bske", x, w["k_proj"])
    v = jnp.einsum("bsd,dke->bske", x, w["v_proj"]) * jnp.asarray(cfg.value_scale, x.dtype)
    q = partial_rope(q, positions, theta, cfg.rotary_dim)
    k = partial_rope(k, positions, theta, cfg.rotary_dim)

    k, v = k.swapaxes(1, 2).astype(cfg.dtype), v.swapaxes(1, 2).astype(cfg.dtype)
    new = write_positions(state, k, v, positions)
    q = q.reshape(B, S, Hk, H // Hk, cfg.qk_head_dim)
    if attn_kind == FULL:
        from ..ops.cache_attention import cache_attention

        out = cache_attention(q, positions, new["k"], new["v"])
    else:
        out = _ring_attend(cfg, q, positions, new, w["sink"])
    return out.reshape(B, S, H * cfg.v_head_dim) @ w["o_proj"], new


def _ring_attend(cfg: MiMoV2Config, q, positions, ring: dict, sink):
    """Queries ``[B, S, Hk, G, dqk]`` against a window layer's whole ring:
    an entry is visible iff the position it records passes the causal and
    the window test; the head's sink logit is one more column of the
    softmax that carries no value."""
    row = positions[:, :, None]  # [B, S, 1]
    held = ring["pos"][:, None, :]  # [B, 1, R]
    visible = (held >= 0) & (held <= row) & (row - held < cfg.window)
    scores = jnp.einsum(
        "bskge,bkte->bkgst", q, ring["k"], preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(cfg.qk_head_dim))
    scores = jnp.where(visible[:, None, None, :, :], scores, jnp.finfo(jnp.float32).min)
    sink = jnp.broadcast_to(
        sink.astype(jnp.float32).reshape(1, *q.shape[2:4], 1, 1), scores.shape[:-1] + (1,)
    )
    probs = jax.nn.softmax(jnp.concatenate([scores, sink], axis=-1), axis=-1)[..., :-1]
    return jnp.einsum("bkgst,bkte->bskge", probs.astype(cfg.dtype), ring["v"])


def dense_mlp(w: dict, x):
    return (jax.nn.silu(x @ w["gate_proj"]) * (x @ w["up_proj"])) @ w["down_proj"]


def forward(cfg: MiMoV2Config, params: dict, cache: dict, tokens, positions):
    """Tokens ``[B, S]`` at ``positions [B, S]`` through every layer and its
    cache: a prefill chunk (one row, S = chunk) and a decode step (every
    slot, S = 1, each row at its own position) alike. Returns (final-norm
    hidden [B, S, D], new cache, this call's counts)."""
    from ..parallel.moe import moe_swiglu_held

    B, S = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
    counts = layer_list.zero_moe_counts(cfg)
    new_cache = {}
    for i, (attn_kind, ff_kind) in enumerate(cfg.layers):
        w = params["layers"][i]
        h = rms_norm(x, w["attn_norm"]["scale"], cfg.rms_eps)
        with jax.named_scope(f"attn_{attn_kind}"):
            a, new_cache[f"layer_{i}"] = attention(
                cfg, attn_kind, w["attn"], cache[f"layer_{i}"], h, positions
            )
        x = x + a
        h = rms_norm(x, w["mlp_norm"]["scale"], cfg.rms_eps)
        if ff_kind == DENSE:
            with jax.named_scope("dense_mlp"):
                x = x + dense_mlp(w["mlp"], h)
        else:
            with jax.named_scope("moe"):
                y, c = moe_swiglu_held(
                    w["moe"], h.reshape(B * S, cfg.d_model),
                    top_k=cfg.top_k, experts_held=cfg.experts_held,
                )
            x = x + y.reshape(B, S, cfg.d_model).astype(x.dtype)
            counts = jax.tree.map(jnp.add, counts, c)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
    return x, new_cache, counts


def _prefill(cfg, params, cache, slot, tokens, positions, n_real=None):
    """A chunk of the one row that lives at ``slot`` of the cache (``n_real``,
    how many of its tokens are the prompt's, changes nothing here: a pad's
    keys are masked by position or overwritten). This
    family takes its row out and writes it back: a ring wraps inside a
    chunk, so its writes are scatters over the row's own entries, and the
    whole row (two full slabs of 10.5 MB, five rings of 1.3 MB at the
    served size) is under 1% of what a 13 ms chunk moves (PERF.md section
    6, PR 31)."""
    row = jax.tree.map(lambda s: jax.lax.dynamic_slice_in_dim(s, slot, 1, 0), cache)
    hidden, row, counts = forward(cfg, params, row, tokens, positions)
    cache = jax.tree.map(
        lambda s, r: jax.lax.dynamic_update_slice_in_dim(s, r, slot, 0), cache, row
    )
    return hidden, cache, counts


def _decode(cfg, params, cache, tok, pos):
    hidden, cache, counts = forward(cfg, params, cache, tok, pos)
    with jax.named_scope("head"):
        return logits(params, hidden[:, -1]), cache, counts
