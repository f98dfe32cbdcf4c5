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

Two published models are this family, told apart by static options of
:class:`MiMoV2Config` (the defaults are MiMo-V2.5's; each option's comment
says whose it is):

- **MiMo-V2.5** (``mimo-v2.5-ep16``, ``mimo-tiny``): q/k heads of 192 beside
  v heads of 128, partial rotary in both attention kinds, a sink logit in
  window layers, ``value_scale``, routed experts alone;
- **K-EXAONE-236B-A23B** (``k-exaone-ep8``, ``k-exaone-tiny``): one head size,
  RMSNorm on each head's q and k (``qk_norm``), rotary in window layers only
  (``rope_full`` off), no sink (``window_sink`` off), ONE shared expert beside
  the routed ones (``d_shared``), the routed sum x ``routed_scale``, and the
  model's own multi-token-prediction block (``mtp``): ``u_i = W_eh
  [RMSNorm(Emb(x_{i+1})) ; RMSNorm(h_i)]`` (``h_i`` the main stack's hidden
  before its final norm) through one more layer of the full-attention,
  expert kind with a slab and a final norm of its own, embedding and head
  shared: its logits at ``i`` predict ``x_{i+2}``. A model with the block
  DRAFTS (``models.serving.Drafter``): a decode step verifies the row's draft
  and leaves the next one, and what is delivered is what the main stack alone
  would deliver.
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
MTP_KIND = (FULL, MOE)  # the multi-token-prediction block's layer (``mtp_layer_types``: full attention)


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
    # Options, each beside the model that sets it (MiMo-V2.5 leaves all six):
    window_sink: bool = True  # MiMo: a window layer's learned sink logits; K-EXAONE: none
    qk_norm: bool = False  # K-EXAONE: RMSNorm, a learned scale [head size], on every head's q and k
    rope_full: bool = True  # MiMo: rotary in both kinds; K-EXAONE: in window layers only
    d_shared: int = 0  # K-EXAONE: one shared SwiGLU expert of this width beside the routed ones
    routed_scale: float = 1.0  # K-EXAONE: ``routed_scaling_factor`` on the routed experts' sum
    mtp: bool = False  # K-EXAONE: the multi-token-prediction block, its slab, and a step that drafts

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
        drafts = {}
        if self.mtp:
            from .serving import Drafter

            drafts = dict(
                # The chunk's hidden states come before the final norm: the block takes them so.
                finish=functools.partial(_finish, self),
                drafter=Drafter(
                    verify=functools.partial(_verify, self),
                    draft=functools.partial(_draft, self),
                    first=functools.partial(_draft_first, self),
                ),
            )
        return ServingModel(
            cfg=self,
            # init_params looks init_layer / init_outer / init_mtp up when called.
            init_params=functools.partial(init_params, self),
            init_cache=functools.partial(init_cache, self),
            prefill=functools.partial(_prefill, self),
            decode=functools.partial(_decode, self),
            logits=logits,
            decode_reads_per_row=reads_per_row(),
            counts=zero_counts(self),
            gauges=cache_bytes,
            derive=functools.partial(derived_stats, self),
            **drafts,
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


# K-EXAONE's options, alike at both sizes.
_K_EXAONE = dict(
    window_sink=False, qk_norm=True, rope_full=False, routed_scale=2.5, mtp=True, value_scale=1.0,
    # ``LLLG`` with a leading dense layer: layers 0-4 of the published 48.
    layers=((WINDOW, DENSE), (WINDOW, MOE), (WINDOW, MOE), (FULL, MOE), (WINDOW, MOE)),
)


def k_exaone_ep8(**over) -> MiMoV2Config:
    """K-EXAONE-236B-A23B's published widths as ONE of 8 chips that share
    each layer by their experts: experts 0-15 of the 128 of every sparse
    layer and of the multi-token-prediction block here, an eighth of the
    vocabulary (19,200 rows of embedding and of head); attention, router
    and the shared expert whole. Layers 0-4 of the published 48 (window +
    dense, window, window, full, window: the leading dense layer and one
    whole ``LLLG`` period) and the block, the rest lying on further chips as
    pipeline stages."""
    base = dict(
        vocab_size=19_200, d_model=6144, n_heads=64, qk_head_dim=128, v_head_dim=128, rotary_dim=128,
        n_kv_heads_full=8, n_kv_heads_window=8, rope_theta=1e6, window_rope_theta=1e6, window=128,
        d_ff=18_432, d_expert=2048, d_shared=2048, router_width=128, experts_held=(0, 16), top_k=8,
        **_K_EXAONE,
    )
    return make_config(base, over)


def k_exaone_tiny(**over) -> MiMoV2Config:
    """The same structure at test size: every option of the model present (a
    window shorter than a prompt, q/k norms, a full layer without rotary, a
    shared expert, 16 experts top-4 of which 4 are held, the block)."""
    base = dict(
        vocab_size=256, d_model=64, n_heads=8, qk_head_dim=16, v_head_dim=16, rotary_dim=16,
        n_kv_heads_full=2, n_kv_heads_window=2, rope_theta=1e6, window_rope_theta=1e6, window=8,
        d_ff=128, d_expert=32, d_shared=32, router_width=16, experts_held=(0, 4), top_k=4,
        dtype=jnp.float32, param_dtype=jnp.float32, max_decode_len=128, **_K_EXAONE,
    )
    return make_config(base, over)


# Presets by the name a job's ``--config`` gives (models/serving.py).
CONFIGS = {
    "mimo-v2.5-ep16": "mimo_v2_5_ep16",
    "mimo-tiny": "mimo_v2_tiny",
    "k-exaone-ep8": "k_exaone_ep8",
    "k-exaone-tiny": "k_exaone_tiny",
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
    if attn_kind == WINDOW and cfg.window_sink:
        out[("attn", "sink")] = ((H,), 0, f32)
    if cfg.qk_norm:
        out[("attn", "q_norm")] = out[("attn", "k_norm")] = ((dqk,), None, f32)
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
        if cfg.d_shared:
            Fs = cfg.d_shared
            out.update({
                ("shared", "gate_proj"): ((D, Fs), D, w),
                ("shared", "up_proj"): ((D, Fs), D, w),
                ("shared", "down_proj"): ((Fs, D), Fs, w),
            })
    return out


def mtp_shapes(cfg: MiMoV2Config) -> dict:
    """``path -> (shape, fan_in, dtype)`` of the multi-token-prediction block:
    the two norms of its inputs, the product that joins them, one layer of the
    full-attention, expert kind and a final norm; embedding and head are the
    main model's."""
    D, f32 = cfg.d_model, jnp.float32
    out = {("block",) + path: leaf for path, leaf in layer_shapes(cfg, MTP_KIND).items()}
    out.update({
        ("enorm", "scale"): ((D,), None, f32),
        ("hnorm", "scale"): ((D,), None, f32),
        ("eh_proj",): ((2 * D, D), 2 * D, cfg.param_dtype),
        ("final_norm", "scale"): ((D,), None, f32),
    })
    return out


def init_layer(cfg: MiMoV2Config, kind: tuple[str, str], key, layer) -> dict:
    """Layer ``layer``'s leaves (``layer`` may be traced: layers of one kind
    share a compiled program)."""
    return layer_list.draw(jax.random.fold_in(key, layer), layer_shapes(cfg, kind))


def init_outer(cfg: MiMoV2Config, key) -> dict:
    return layer_list.draw(jax.random.fold_in(key, 1 << 20), layer_list.outer_shapes(cfg))


def init_mtp(cfg: MiMoV2Config, key) -> dict:
    return layer_list.draw(jax.random.fold_in(key, 1 << 21), mtp_shapes(cfg))


def init_params(cfg: MiMoV2Config, key) -> dict:
    """The serving tree, a layer at a time (``layer_list.init_params``),
    with the multi-token-prediction block under ``mtp`` where the model has
    one. ``init_layer``, ``init_outer`` and ``init_mtp`` are looked up at
    call time, so a caller that brings its own seeded leaves (the benchmark)
    replaces them."""
    params = layer_list.init_params(cfg, key, cfg.layers, init_outer, init_layer)
    if cfg.mtp:
        params["mtp"] = jax.jit(lambda k: init_mtp(cfg, k))(key)
    return params


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
    if cfg.mtp:
        Hk = cfg.n_kv_heads(FULL)
        cache["mtp"] = {
            "k": jnp.zeros((slots, Hk, L, cfg.qk_head_dim), cfg.dtype),
            "v": jnp.zeros((slots, Hk, L, cfg.v_head_dim), cfg.dtype),
        }
    return cache


def cache_bytes(cache: dict) -> dict:
    """The gauges: bytes held by the full layers' slabs, by the window
    layers' rings and, where the model has the block, by its slab."""
    size = lambda state: sum(a.size * a.dtype.itemsize for a in state.values())
    layers = [s for name, s in cache.items() if name != "mtp"]
    out = {
        "cache_full_bytes": sum(size(s) for s in layers if "pos" not in s),
        "cache_window_bytes": sum(size(s) for s in layers if "pos" in s),
    }
    if "mtp" in cache:
        out["cache_mtp_bytes"] = size(cache["mtp"])
    return out


# ---- the device counters: the expert layers' and, where the model drafts, the drafts' ----


def zero_counts(cfg: MiMoV2Config) -> dict:
    counts = layer_list.zero_moe_counts(cfg)
    if cfg.mtp:
        # Row-steps that verified a draft, and those whose draft was the main stack's own choice.
        counts.update(mtp_drafts=jnp.zeros((), jnp.int32), mtp_accepted=jnp.zeros((), jnp.int32))
    return counts


def derived_stats(cfg: MiMoV2Config, n: dict) -> dict:
    out = layer_list.derived_moe_stats(cfg, n)
    if cfg.mtp:
        drafts = int(n["mtp_drafts"])
        out["mtp_accept_pct"] = round(100.0 * int(n["mtp_accepted"]) / drafts, 4) if drafts else None
    return out


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
    v = jnp.einsum("bsd,dke->bske", x, w["v_proj"])
    if cfg.value_scale != 1.0:
        v = v * jnp.asarray(cfg.value_scale, x.dtype)
    if cfg.qk_norm:
        q, k = rms_norm(q, w["q_norm"], cfg.rms_eps), rms_norm(k, w["k_norm"], cfg.rms_eps)
    if attn_kind == WINDOW or cfg.rope_full:
        q = partial_rope(q, positions, theta, cfg.rotary_dim)
        k = partial_rope(k, positions, theta, cfg.rotary_dim)

    k, v = k.swapaxes(1, 2).astype(cfg.dtype), v.swapaxes(1, 2).astype(cfg.dtype)
    new = write_positions(state, k, v, positions)
    q = q.reshape(B, S, Hk, H // Hk, cfg.qk_head_dim)
    if attn_kind == FULL:
        from ..ops.cache_attention import cache_attention

        out = cache_attention(q, positions, new["k"], new["v"])
    else:
        out = _ring_attend(cfg, q, positions, new, w.get("sink"))
    return out.reshape(B, S, H * cfg.v_head_dim) @ w["o_proj"], new


def _ring_attend(cfg: MiMoV2Config, q, positions, ring: dict, sink):
    """Queries ``[B, S, Hk, G, dqk]`` against a window layer's whole ring:
    an entry is visible iff the position it records passes the causal and
    the window test; the head's sink logit (None: the model has none) is one
    more column of the softmax that carries no value."""
    row = positions[:, :, None]  # [B, S, 1]
    held = ring["pos"][:, None, :]  # [B, 1, R]
    visible = (held >= 0) & (held <= row) & (row - held < cfg.window)
    scores = jnp.einsum(
        "bskge,bkte->bkgst", q, ring["k"], preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(cfg.qk_head_dim))
    scores = jnp.where(visible[:, None, None, :, :], scores, jnp.finfo(jnp.float32).min)
    if sink is None:
        probs = jax.nn.softmax(scores, axis=-1)
    else:
        sink = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, *q.shape[2:4], 1, 1), scores.shape[:-1] + (1,)
        )
        probs = jax.nn.softmax(jnp.concatenate([scores, sink], axis=-1), axis=-1)[..., :-1]
    return jnp.einsum("bkgst,bkte->bskge", probs.astype(cfg.dtype), ring["v"])


def dense_mlp(w: dict, x):
    return (jax.nn.silu(x @ w["gate_proj"]) * (x @ w["up_proj"])) @ w["down_proj"]


def layer(cfg: MiMoV2Config, kind, w: dict, state: dict, x, positions, attn_scope: str, moe_scope: str = "moe"):
    """One pre-norm residual layer of ``kind`` on ``x [B, S, D]`` through its
    cache ``state``, its attention under the device scope ``attn_scope`` and
    its experts under ``moe_scope``. Returns (x, new state, the expert
    layer's counts or None)."""
    from ..parallel.moe import moe_held

    attn_kind, ff_kind = kind
    B, S, _ = x.shape
    h = rms_norm(x, w["attn_norm"]["scale"], cfg.rms_eps)
    with jax.named_scope(attn_scope):
        a, state = attention(cfg, attn_kind, w["attn"], state, h, positions)
    x = x + a
    h = rms_norm(x, w["mlp_norm"]["scale"], cfg.rms_eps)
    if ff_kind == DENSE:
        with jax.named_scope("dense_mlp"):
            return x + dense_mlp(w["mlp"], h), state, None
    with jax.named_scope(moe_scope):
        flat = h.reshape(B * S, cfg.d_model)
        y, counts = moe_held(
            w["moe"], flat, top_k=cfg.top_k, experts_held=cfg.experts_held, weight_scale=cfg.routed_scale
        )
        if cfg.d_shared:
            with jax.named_scope("moe_shared"):
                y = y + dense_mlp(w["shared"], flat)
    return x + y.reshape(B, S, cfg.d_model).astype(x.dtype), state, counts


def stack(cfg: MiMoV2Config, params: dict, cache: dict, tokens, positions):
    """Tokens ``[B, S]`` at ``positions [B, S]`` through every layer of the
    main stack and its cache. Returns (the hidden states BEFORE the final
    norm [B, S, D], the new cache (a block's slab passed through), this
    call's counts)."""
    with jax.named_scope("embed"):
        x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
    counts = zero_counts(cfg)
    new_cache = dict(cache)
    for i, kind in enumerate(cfg.layers):
        name = f"layer_{i}"
        x, new_cache[name], c = layer(
            cfg, kind, params["layers"][i], cache[name], x, positions, f"attn_{kind[0]}"
        )
        if c is not None:
            counts = _add_counts(counts, c)
    return x, new_cache, counts


def forward(cfg: MiMoV2Config, params: dict, cache: dict, tokens, positions):
    """:func:`stack` and the final norm: a prefill chunk (one row, S =
    chunk) and a decode step (every slot, each row at its own position)
    alike. Returns (final-norm hidden [B, S, D], new cache, this call's
    counts)."""
    x, new_cache, counts = stack(cfg, params, cache, tokens, positions)
    return rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps), new_cache, counts


def mtp_block(cfg: MiMoV2Config, params: dict, slab: dict, h, next_tokens, positions):
    """The multi-token-prediction block at ``positions [B, S]``: the main
    stack's hidden states ``h [B, S, D]`` there (before its final norm)
    joined with the embedding of each position's NEXT token, through the
    block's one layer and its slab. All of it under the device scope
    ``mtp`` with ``mtp_attn`` and ``mtp_moe`` inside (not the main
    stack's names: a reader that charges time by scope name must not count
    the block twice). Returns (its final-norm hidden [B, S, D], whose logits
    predict the token after next, the new slab, the expert layer's counts)."""
    w = params["mtp"]
    with jax.named_scope("mtp"):
        e = params["embed"]["embedding"][next_tokens].astype(cfg.dtype)
        joined = jnp.concatenate(
            [rms_norm(e, w["enorm"]["scale"], cfg.rms_eps), rms_norm(h, w["hnorm"]["scale"], cfg.rms_eps)], axis=-1
        )
        x, slab, counts = layer(cfg, MTP_KIND, w["block"], slab, joined @ w["eh_proj"], positions, "mtp_attn", "mtp_moe")
        return rms_norm(x, w["final_norm"]["scale"], cfg.rms_eps), slab, counts


def _add_counts(counts: dict, c: dict) -> dict:
    return {**counts, **{k: counts[k] + v for k, v in c.items()}}


def _row(tree, slot):
    """Row ``slot`` of every leaf, as a batch of one."""
    return jax.tree.map(lambda s: jax.lax.dynamic_slice_in_dim(s, slot, 1, 0), tree)


def _put_row(tree, row, slot):
    return jax.tree.map(lambda s, r: jax.lax.dynamic_update_slice_in_dim(s, r, slot, 0), tree, row)


def _prefill(cfg, params, cache, slot, tokens, positions, n_real=None):
    """A chunk of the one row that lives at ``slot`` of the cache (``n_real``,
    how many of its tokens are the prompt's, changes nothing here: a pad's
    keys are masked by position or overwritten). This
    family takes its row out and writes it back: a ring wraps inside a
    chunk, so its writes are scatters over the row's own entries, and the
    whole row (two full slabs of 10.5 MB, five rings of 1.3 MB at the
    served size) is under 1% of what a 13 ms chunk moves (PERF.md section
    6, PR 31). A model that drafts gets the chunk's tokens with the one
    that follows them (``[1, chunk + 1]``: models/serving.py) and fills the
    block's slab from them; its hidden states go on before the final norm
    (:func:`_finish` and :func:`_draft_first` take them so)."""
    S = positions.shape[1]
    row = _row(cache, slot)
    hidden, row, counts = stack(cfg, params, row, tokens[:, :S], positions)
    if not cfg.mtp:
        hidden = rms_norm(hidden, params["final_norm"]["scale"], cfg.rms_eps)
    elif tokens.shape[1] > S:
        _, row["mtp"], c = mtp_block(cfg, params, row["mtp"], hidden, tokens[:, 1:], positions)
        counts = _add_counts(counts, c)
    return hidden, _put_row(cache, row, slot), counts


def _decode(cfg, params, cache, tok, pos):
    hidden, cache, counts = forward(cfg, params, cache, tok, pos)
    with jax.named_scope("head"):
        return logits(params, hidden[:, -1]), cache, counts


# ---- a model with the block drafts (models.serving.Drafter) ----


def _finish(cfg, params, cache, slot, h, position):
    """The end of an admission: the prompt's last hidden state ``h [1, D]``
    (before the final norm) to its logits."""
    with jax.named_scope("head"):
        return logits(params, rms_norm(h, params["final_norm"]["scale"], cfg.rms_eps))


def _verify(cfg, params, cache, tokens, positions):
    """The main stack over each row's last accepted token and its draft
    (``[slots, 2]``, at the row's position and the next): float32 logits at
    both, and the hidden states the block drafts from."""
    hidden, cache, counts = stack(cfg, params, cache, tokens, positions)
    with jax.named_scope("head"):
        return logits(params, rms_norm(hidden, params["final_norm"]["scale"], cfg.rms_eps)), hidden, cache, counts


def _draft(cfg, params, cache, hidden, chosen, positions, accepted, live):
    """The block over a step's two positions with the tokens the main stack
    chose there, into its slab; the draft is its choice at the last position
    kept (the second where the row's draft was ``accepted``). ``live``: the
    rows that hold a request, for the counters."""
    x, slab, counts = mtp_block(cfg, params, cache["mtp"], hidden, chosen, positions)
    with jax.named_scope("mtp"), jax.named_scope("head"):
        out = logits(params, jnp.where(accepted[:, None], x[:, 1], x[:, 0]))
    counts = {
        **counts,
        "mtp_drafts": jnp.sum(live, dtype=jnp.int32),
        "mtp_accepted": jnp.sum(accepted & live, dtype=jnp.int32),
    }
    return out, {**cache, "mtp": slab}, _add_counts(zero_counts(cfg), counts)


def _draft_first(cfg, params, cache, slot, h, position, first):
    """The end of an admission, after its first token: the block at the
    prompt's last position (``h [1, D]`` at ``position``, a scalar) with the
    sampled ``first [1]`` as its next token, into row ``slot`` of its slab
    (taken out and put back: 17 MB once a prompt), which leaves the row's
    first draft. Returns (float32 draft logits [1, V], cache)."""
    x, row, _ = mtp_block(cfg, params, _row(cache["mtp"], slot), h[:, None], first[:, None], jnp.reshape(position, (1, 1)))
    with jax.named_scope("mtp"), jax.named_scope("head"):
        return logits(params, x[:, 0]), {**cache, "mtp": _put_row(cache["mtp"], row, slot)}
