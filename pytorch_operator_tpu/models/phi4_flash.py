"""A decoder-hybrid-decoder for serving (the ``phi4_flash`` family): a
SELF-decoder of alternating Mamba-1 and window-attention layers that ends in
ONE full-attention layer, and a CROSS-decoder that alternates gated memory
units with cross-attention onto that one layer's keys and values.

Reference analog: none (the reference is a training operator). Beside the
other layer-list families (``models/mimo_v2.py``, ``models/nemotron_h.py``)
this one has layers that own NO state and read what another layer made:

- half of the layers keep nothing. With ``n`` layers, layers ``0 .. n/2``
  (even) are Mamba-1 and own a convolution tail and a float32 scan state;
  layers ``1 .. n/2 - 1`` (odd) attend a window and own a ring; layer ``n/2
  + 1`` attends everything and owns the ONLY slab of ``max_decode_len``
  positions; the odd layers after it own nothing and attend THAT slab with
  queries of their own; the even layers after it own nothing and gate ``m``,
  the scan output of layer ``n/2`` at the same token, which travels down the
  stack inside a forward and is no cache leaf;
- so a prompt's tokens before the last need the self-decoder only: the
  cross-decoder writes no state. :func:`_prefill` runs layers ``0 .. n/2``
  and layer ``n/2 + 1``'s norm, key/value projection and slab write, and
  hands on the residual entering that layer beside ``m``; :func:`finish`
  (``ServingModel.finish``) runs the rest for the prompt's last token, once
  a prompt, against the slot's slab; a decode step runs every layer;
- attention is DIFFERENTIAL (the difference of two softmaxes over paired
  heads, a learned scalar and a norm per pair) and has no position
  embedding; norms are LayerNorm with bias; the embedding is the head.

The Mamba-1 layer's contract with the engine is ``models/nemotron_h.py``'s:
a chunk freezes the state beyond its ``n_real`` tokens and starts a row from
ZERO state where it stands at position 0 (``prefill_state_resets`` =
``admitted``); parked rows' state runs on and is discarded.

The equations, for layer ``l`` (``x <- x + mixer(LN(x))``, ``x <- x + W2 (u
* silu(g))`` with ``[g | u] = LN'(x) W1``; final LN; ``logits = x E^T``):

- Mamba-1: ``[u | z] = x W_in``; depthwise causal convolution of ``d_conv``
  taps with bias, ``silu``; ``[delta | B | C] = u W_x``; ``dt = softplus(delta
  W_dt + b_dt)``; ``A = -exp(A_log)``; ``S_t[n, c] = exp(dt_t[c] A[n, c])
  S_{t-1}[n, c] + dt_t[c] B_t[n] u_t[c]``, ``y_t[c] = sum_n S_t[n, c] C_t[n] +
  D[c] u_t[c]``; ``out = (y * silu(z)) W_out``. The decay is per channel AND
  per state index, so ``nemotron_h.scan_chunk``'s decay-masked ``C B^T``
  product (one scalar decay a head) does not apply. The mixer and its scan
  (two forms that agree, ``scan_step`` and ``scan_chunk``; which runs is
  decided by the call's shape) are ``models/ssm.py``'s: the tree's one
  Mamba-1 mixer, which ``models/jamba.py`` runs too.
- differential attention: ``q = x W_q + b_q`` as pairs ``(q1_i, q2_i)`` of
  adjacent heads, ``[k | v] = x W_kv + b_kv`` as pairs ``(k1_g, k2_g)`` and
  ``v_g = [v1_g | v2_g]`` of twice the head size; pair ``i`` uses ``g = i //
  (heads / kv heads)``; ``o_i = sum_t (P1_t - lam P2_t) v_g,t`` with ``P =
  softmax(q k / sqrt(head_dim))`` over the visible ``t``; ``o_i <- (1 -
  lam_init) gamma * o_i / rms(o_i)``; ``out = o W_o + b_o``; ``lam = exp(lq1 .
  lk1) - exp(lq2 . lk2) + lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3 l)``.
- cross-attention: the same with ``q`` alone and layer ``n/2 + 1``'s slab.
- gated memory unit: ``out = (silu(x W_g) * m) W_o``.

TPU-first shape: everything static; every cache leaf leads with the slot
axis. A key pair and a value pair are stored side by side, ``[slots, pairs,
positions, 2 head_dim]``: 128 lanes at the published head size of 64, where
single heads of 64 would be padded to twice their bytes. Both softmaxes of a
pair then share ONE walk over the slab (``ops/cache_attention.py`` as it
stands): the pair's two queries are widened to ``2 head_dim`` with zeros
where the other's key lies, so ``[q1 | 0] . [k1 | k2] = q1 . k1``; keys are
read once, values once, and the two results come back side by side. The scan
state is ``[slots, d_state, d_inner]``, channels minor (``d_state`` = 16
minor would be padded to 128 lanes, 8 times its bytes). Serving only: no
training path (the scan has no backward here), no weight or cache
quantisation, no state snapshots.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import layer_list, ssm
from .ssm import scan_chunk, scan_step  # noqa: F401 (the family's tests and tools find the scan by these names)

Dtype = Any

MAMBA, MAMBA_MEMORY, WINDOW, FULL, GMU, CROSS = (
    "mamba", "mamba_memory", "attn_window", "attn_full", "gmu", "attn_cross",
)
F32 = jnp.float32

@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200_064
    d_model: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    d_ff: int = 10_240
    window: int = 512
    # Mamba-1 (the phi4flash configuration class's defaults)
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    ln_eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    # The serving dtype: matrices are MADE in it, a layer at a time (norms,
    # biases, the convolution, A_log, dt_bias, D and the lambda vectors stay
    # float32).
    param_dtype: Dtype = jnp.bfloat16
    decode: bool = False
    max_decode_len: int = 4096

    def __post_init__(self):
        if self.n_layers < 4 or self.n_layers % 2:
            raise ValueError(f"n_layers={self.n_layers}: a self-decoder and a cross-decoder need an even count >= 4")
        if self.d_model % self.n_heads or self.n_heads % self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError("heads must divide the width, key/value heads the heads, and pair up")

    @property
    def layers(self) -> tuple[str, ...]:
        """Each layer's kind: (its mixer, what it owns)."""
        half = self.n_layers // 2
        kinds = []
        for l in range(self.n_layers):
            if l <= half:
                kinds.append((MAMBA_MEMORY if l == half else MAMBA) if l % 2 == 0 else WINDOW)
            else:
                kinds.append(FULL if l == half + 1 else (GMU if l % 2 == 0 else CROSS))
        return tuple(kinds)

    @property
    def full_layer(self) -> int:
        return self.n_layers // 2 + 1

    @property
    def full_readers(self) -> int:
        """Layers that attend the one slab: its owner and the cross layers."""
        return sum(kind in (FULL, CROSS) for kind in self.layers)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_pairs(self) -> int:
        return self.n_kv_heads // 2

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return -(-self.d_model // 16)

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
            finish=functools.partial(finish, self),
            slab_reads=functools.partial(slab_reads, self),
            decode_reads_per_row=reads_per_row(),
            counts=zero_counts(),
            gauges=functools.partial(cache_gauges, self),
        )


def make_config(base: dict, over: dict) -> Phi4FlashConfig:
    """A preset with the server's overrides; this family serves plain
    bfloat16 weights and cache."""
    over = dict(over)
    for knob in ("quantize", "kv_quantize"):
        if over.pop(knob, None):
            raise ValueError(f"the phi4_flash family serves unquantised weights and cache: no {knob}")
    return Phi4FlashConfig(**{**base, **over})


def phi4_mini_flash(**over) -> Phi4FlashConfig:
    """Phi-4-mini-flash-reasoning as published, whole on one chip: 32 layers
    (9 Mamba-1, 8 window, 1 full, 7 gated memory units, 7 cross-attention),
    3.85 B parameters."""
    return make_config({}, over)


def phi4_flash_tiny(**over) -> Phi4FlashConfig:
    """The same structure at test size: every kind of layer present (two
    Mamba layers before the one that hands its memory on, two windows
    shorter than a prompt, the full layer, a memory unit and a cross layer)."""
    base = dict(
        vocab_size=256, d_model=64, n_layers=8, n_heads=8, n_kv_heads=4, d_ff=128, window=8,
        d_state=8, dtype=jnp.float32, param_dtype=jnp.float32, max_decode_len=128,
    )
    return make_config(base, over)


# Presets by the name a job's ``--config`` gives (models/serving.py).
CONFIGS = {
    "phi4-mini-flash": "phi4_mini_flash",
    "phi4-flash-tiny": "phi4_flash_tiny",
}


# ---- parameters: made in the serving dtype, a layer at a time ----

# Standard deviations of the seeded small leaves (as ``layer_list.draw``'s
# fan_in = 1 / variance): biases 0.02 (zero would leave them untested), the
# lambda vectors 0.1.
BIAS, LAMBDA = 2500, 100


def layer_shapes(cfg: Phi4FlashConfig, kind: str) -> dict:
    """``path -> (shape, fan_in, dtype)`` of one layer's leaves, as
    ``layer_list.draw`` reads them. A matrix that writes to the residual
    stream is drawn ``1 / sqrt(2 n_layers)`` smaller. ``A_log``, ``dt_bias``,
    ``dt_proj`` and ``D`` are made in :func:`init_layer`."""
    D, F, w = cfg.d_model, cfg.d_ff, cfg.param_dtype
    res = 2 * cfg.n_layers
    out = {
        ("norm1", "scale"): ((D,), None, F32), ("norm1", "bias"): ((D,), BIAS, F32),
        ("norm2", "scale"): ((D,), None, F32), ("norm2", "bias"): ((D,), BIAS, F32),
        ("mlp", "gate_up"): ((D, 2 * F), D, w), ("mlp", "down"): ((F, D), F * res, w),
    }
    if kind in (MAMBA, MAMBA_MEMORY):
        shapes = ssm.mixer_shapes(D, cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank, w, cfg.d_inner * res)
        out.update({("ssm", name): leaf for name, leaf in shapes.items()})
    elif kind == GMU:
        di = cfg.d_inner
        out.update({("gmu", "in_proj"): ((D, di), D, w), ("gmu", "out_proj"): ((di, D), di * res, w)})
    else:
        d = cfg.head_dim
        out.update({
            ("attn", "q_proj"): ((D, D), D, w), ("attn", "q_bias"): ((D,), BIAS, F32),
            ("attn", "o_proj"): ((D, D), D * res, w), ("attn", "o_bias"): ((D,), BIAS, F32),
            ("attn", "subln"): ((2 * d,), None, F32),
            **{("attn", f"lambda_{n}"): ((d,), LAMBDA, F32) for n in ("q1", "k1", "q2", "k2")},
        })
        if kind != CROSS:
            kv = 2 * cfg.n_kv_heads * d
            out.update({("attn", "kv_proj"): ((D, kv), D, w), ("attn", "kv_bias"): ((kv,), BIAS, F32)})
    return out


def init_layer(cfg: Phi4FlashConfig, kind: str, key, layer) -> dict:
    """Layer ``layer``'s leaves (``layer`` may be traced: layers of one kind
    share a compiled program). A Mamba layer's ``A_log[n, c]`` is ``log(n +
    1)``, its ``dt_bias`` the inverse softplus of a step log-uniform in
    [1e-3, 1e-1], its ``D`` ones, its ``dt_proj`` uniform in ``+- dt_rank ^
    -1/2``: the published Mamba initialisation."""
    key = jax.random.fold_in(key, layer)
    tree = layer_list.draw(key, layer_shapes(cfg, kind))
    if kind in (MAMBA, MAMBA_MEMORY):
        tree["ssm"].update(
            ssm.published_init(jax.random.fold_in(key, 1 << 10), cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.param_dtype)
        )
    return tree


def outer_shapes(cfg: Phi4FlashConfig) -> dict:
    """The embedding, which is also the head, and the final norm."""
    D = cfg.d_model
    return {
        ("embed", "embedding"): ((cfg.vocab_size, D), D, cfg.param_dtype),
        ("final_norm", "scale"): ((D,), None, F32),
        ("final_norm", "bias"): ((D,), BIAS, F32),
    }


def init_outer(cfg: Phi4FlashConfig, key) -> dict:
    return layer_list.draw(jax.random.fold_in(key, 1 << 20), outer_shapes(cfg))


def init_params(cfg: Phi4FlashConfig, key) -> dict:
    """The serving tree, a layer at a time (``layer_list.init_params``).
    ``init_layer`` and ``init_outer`` are looked up at call time, so a
    caller that brings its own seeded leaves (the benchmark) replaces those
    two."""
    return layer_list.init_params(cfg, key, cfg.layers, init_outer, init_layer)


# ---- the cache: a state only for the layers that own one ----


def ring_len(cfg: Phi4FlashConfig, chunk: int) -> int:
    """Positions a window layer keeps: a chunk's queries see back ``window -
    1`` positions before the chunk's first, and the chunk's own ``chunk``
    positions are written before it attends; rounded up to whole chunks, so
    that a chunk (which starts at a multiple of ``chunk``) never wraps and
    is written in place."""
    return min(cfg.max_decode_len, -(-(cfg.window + chunk) // chunk) * chunk)


def init_cache(cfg: Phi4FlashConfig, slots: int, chunk: int) -> dict:
    """``layer_i -> {conv, state}`` for a Mamba layer (the last ``d_conv -
    1`` inputs of its convolution; its scan's float32 state, channels
    minor), ``{k, v, pos}`` for a window layer (a ring; ``pos`` the position
    each entry holds, -1 = none yet), ``{k, v}`` for THE full layer
    (``max_decode_len`` positions); the cross-decoder's layers own nothing.
    Keys and values lie as pairs, ``[slots, kv_pairs, positions, 2
    head_dim]``. Every leaf leads with the slot axis."""
    P, W, R = cfg.kv_pairs, 2 * cfg.head_dim, ring_len(cfg, chunk)
    kv = lambda T: {"k": jnp.zeros((slots, P, T, W), cfg.dtype), "v": jnp.zeros((slots, P, T, W), cfg.dtype)}
    cache = {}
    for i, kind in enumerate(cfg.layers):
        if kind in (MAMBA, MAMBA_MEMORY):
            cache[f"layer_{i}"] = ssm.init_state(slots, cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dtype)
        elif kind == WINDOW:
            cache[f"layer_{i}"] = {**kv(R), "pos": jnp.full((slots, R), -1, jnp.int32)}
        elif kind == FULL:
            cache[f"layer_{i}"] = kv(cfg.max_decode_len)
    return cache


def cache_gauges(cfg: Phi4FlashConfig, cache: dict) -> dict:
    """Bytes held by the one slab, by the rings and by the Mamba layers'
    constant state, and how many layers read the slab."""
    size = lambda state: sum(a.size * a.dtype.itemsize for a in state.values())
    of = lambda pick: sum(size(s) for s in cache.values() if pick(s))
    return {
        "cache_full_bytes": of(lambda s: "k" in s and "pos" not in s),
        "cache_window_bytes": of(lambda s: "pos" in s),
        "cache_state_bytes": of(lambda s: "state" in s),
        "cache_full_readers": cfg.full_readers,
    }


def zero_counts() -> dict:
    """The chunks that started a row from zero state, and the tokens the
    cross-decoder ran on outside ``decode_block``."""
    return {"prefill_state_resets": jnp.zeros((), jnp.int32), "prefill_cross_tokens": jnp.zeros((), jnp.int32)}


def slab_reads(cfg: Phi4FlashConfig, chunk_ends, p: int):
    """What an admission's programs read of the slab, as the positions each
    read needs (``ServingModel.slab_reads``): the chunks attend no slab, and
    the finish attends the prompt's ``p`` positions once a reader."""
    return np.full((cfg.full_readers,), p)


# ---- the mixers ----


def layer_norm(x, w: dict, eps: float):
    x32 = x.astype(F32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * w["scale"] + w["bias"]).astype(x.dtype)


dense_mlp = layer_list.gated_mlp


def ssm_mixer(cfg: Phi4FlashConfig, w: dict, cache: dict, x, *, slot=None, fresh=None, n_real=None):
    """A Mamba-1 layer for ``x [B, S, D]``: the tree's one Mamba-1 mixer
    (``models/ssm.py``: a chunk of row ``slot`` whose first ``n_real`` tokens
    move the state, from zero where ``fresh``; or a decode step over every
    row), which reads its sizes off the leaves. This family's layers have no
    inner norms. Returns ``(out [B, S, D], m [B, S, d_inner], new cache)``;
    ``m`` is the scan's output with the ``D`` term, before the gate: what the
    gated memory units of the same token read. The forward calls it by this
    name, which is where a test replaces it."""
    return ssm.mamba1_mixer(w, cache, x, slot=slot, fresh=fresh, n_real=n_real)


def gated_memory(w: dict, x, m):
    return (jax.nn.silu(x @ w["in_proj"]) * m) @ w["out_proj"]


def paired_queries(cfg: Phi4FlashConfig, w: dict, x):
    """``x W_q + b_q`` as ``[B, S, kv_pairs, G, 2 head_dim]``: the ``G = 2
    heads / kv_heads`` queries that read key pair ``g``, in order ``(q1,
    q2)`` of each query pair, each widened with zeros where the OTHER key of
    the pair lies (``[q1 | 0]``, ``[0 | q2]``), and scaled by ``sqrt 2`` in
    float32: the score against a stored pair is then ``q . k / sqrt(head_dim)``
    under a division by ``sqrt(2 head_dim)``."""
    B, S, _ = x.shape
    d, P = cfg.head_dim, cfg.kv_pairs
    q = (jnp.dot(x, w["q_proj"], preferred_element_type=F32) + w["q_bias"]) * math.sqrt(2.0)
    q = q.astype(x.dtype).reshape(B, S, P, -1, 2, d)  # [.., query pair of the group, which of the pair, d]
    q1, q2, zero = q[..., 0, :], q[..., 1, :], jnp.zeros_like(q[..., 0, :])
    wide = jnp.stack([jnp.concatenate([q1, zero], axis=-1), jnp.concatenate([zero, q2], axis=-1)], axis=-2)
    # The zeros stay zeros: a compiler that folded them away would slice the stored pairs apart along their minor axis.
    return jax.lax.optimization_barrier(wide.reshape(B, S, P, -1, 2 * d))


def paired_kv(cfg: Phi4FlashConfig, w: dict, x):
    """``x W_kv + b_kv`` as key pairs and value pairs ``[B, kv_pairs, S, 2
    head_dim]`` each (adjacent heads pair, so a pair is contiguous)."""
    B, S, _ = x.shape
    kv = (jnp.dot(x, w["kv_proj"], preferred_element_type=F32) + w["kv_bias"]).astype(cfg.dtype)
    kv = kv.reshape(B, S, 2, cfg.kv_pairs, 2 * cfg.head_dim).transpose(2, 0, 3, 1, 4)
    return kv[0], kv[1]


def differential(cfg: Phi4FlashConfig, w: dict, layer: int, attended, dtype):
    """``attended [B, S, kv_pairs, G, 2 head_dim]``, the two softmaxes'
    weighted values side by side for each query pair, to the layer's output
    ``[B, S, D]``: the difference under the learned ``lam``, RMSNorm over
    the pair's ``2 head_dim``, the output projection."""
    B, S, P, G, W = attended.shape
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (
        jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"])) - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + lam_init
    )
    a = attended.astype(F32).reshape(B, S, P, G // 2, 2, W)
    o = a[..., 0, :] - lam * a[..., 1, :]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.ln_eps) * w["subln"] * (1.0 - lam_init)
    return jnp.dot(o.reshape(B, S, -1).astype(dtype), w["o_proj"], preferred_element_type=F32) + w["o_bias"]


def write_kv(cache: dict, k, v, positions, slot):
    """The incoming pairs written where they belong: a chunk at row ``slot``
    in place (it never wraps: :func:`ring_len`), a decode step a position a
    row; a ring also records the positions."""
    if slot is None:
        return layer_list.write_positions(cache, k, v, positions)
    at = positions[0, 0] % cache["k"].shape[2]
    new = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k, (slot, 0, at, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v, (slot, 0, at, 0)),
    }
    if "pos" in cache:
        new["pos"] = jax.lax.dynamic_update_slice(cache["pos"], positions, (slot, at))
    return new


def ring_attend(cfg: Phi4FlashConfig, q, positions, ring: dict, slot):
    """Paired queries ``[B, S, P, G, 2d]`` against a window layer's whole
    ring (row ``slot`` of it for a chunk): an entry is visible iff the
    position it records passes the causal and the window test."""
    if slot is not None:
        ring = {name: jax.lax.dynamic_slice_in_dim(leaf, slot, 1, 0) for name, leaf in ring.items()}
    row = positions[:, :, None]  # [B, S, 1]
    held = ring["pos"][:, None, :]  # [B, 1, R]
    visible = (held >= 0) & (held <= row) & (row - held < cfg.window)
    scores = jnp.einsum("bspgd,bptd->bpgst", q, ring["k"], preferred_element_type=F32) / math.sqrt(q.shape[-1])
    scores = jnp.where(visible[:, None, None, :, :], scores, jnp.finfo(F32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bpgst,bptd->bspgd", probs.astype(q.dtype), ring["v"])


def slab_attention(cfg: Phi4FlashConfig, w: dict, layer: int, slab: dict, x, positions, slot):
    """Differential attention of ``x``'s own queries against the one slab
    (already holding every position up to the queries'): what the full layer
    does after its write, and all a cross layer does."""
    from ..ops.cache_attention import cache_attention

    attended = cache_attention(paired_queries(cfg, w, x), positions, slab["k"], slab["v"], slot=slot)
    return differential(cfg, w, layer, attended, x.dtype)


# ---- the forward, in the three pieces the engine's programs are made of ----


def self_decoder(cfg: Phi4FlashConfig, params: dict, cache: dict, tokens, positions, *, slot=None, n_real=None):
    """Tokens ``[B, S]`` through layers ``0 .. n/2`` and the full layer's
    key/value write. Returns (the residual entering the full layer ``[B, S,
    D]``, ``m``, the new cache, whether the chunk started its row afresh)."""
    with jax.named_scope("embed"):
        x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
    # A chunk that stands at position 0 starts its row from zero state.
    fresh = None if slot is None else positions[0, 0] == 0
    new_cache, m = {}, None
    for i, kind in enumerate(cfg.layers[: cfg.full_layer]):
        w, name = params["layers"][i], f"layer_{i}"
        h = layer_norm(x, w["norm1"], cfg.ln_eps)
        if kind == WINDOW:
            with jax.named_scope("attn_window"):
                k, v = paired_kv(cfg, w["attn"], h)
                new_cache[name] = write_kv(cache[name], k, v, positions, slot)
                attended = ring_attend(cfg, paired_queries(cfg, w["attn"], h), positions, new_cache[name], slot)
                y = differential(cfg, w["attn"], i, attended, x.dtype)
        else:
            with jax.named_scope("ssm"):
                y, m, new_cache[name] = ssm_mixer(cfg, w["ssm"], cache[name], h, slot=slot, fresh=fresh, n_real=n_real)
        x = x + y.astype(x.dtype)
        with jax.named_scope("dense_mlp"):
            x = x + dense_mlp(w["mlp"], layer_norm(x, w["norm2"], cfg.ln_eps))
    i = cfg.full_layer
    w, name = params["layers"][i], f"layer_{i}"
    with jax.named_scope("attn_full"):
        k, v = paired_kv(cfg, w["attn"], layer_norm(x, w["norm1"], cfg.ln_eps))
        new_cache[name] = write_kv(cache[name], k, v, positions, slot)
    return x, m, new_cache, fresh


def cross_decoder(cfg: Phi4FlashConfig, params: dict, slab: dict, x, m, positions, *, slot=None):
    """The full layer's attention (its keys and values lie in ``slab``
    already) and every layer after it, for ``x [B, S, D]`` entering the full
    layer and ``m`` of the same tokens; nothing is written. Returns the
    final-norm hidden ``[B, S, D]``."""
    for i in range(cfg.full_layer, cfg.n_layers):
        kind, w = cfg.layers[i], params["layers"][i]
        h = layer_norm(x, w["norm1"], cfg.ln_eps)
        if kind == GMU:
            with jax.named_scope("gmu"):
                y = gated_memory(w["gmu"], h, m)
        else:
            with jax.named_scope(kind):
                y = slab_attention(cfg, w["attn"], i, slab, h, positions, slot)
        x = x + y.astype(x.dtype)
        with jax.named_scope("dense_mlp"):
            x = x + dense_mlp(w["mlp"], layer_norm(x, w["norm2"], cfg.ln_eps))
    return layer_norm(x, params["final_norm"], cfg.ln_eps)


logits = layer_list.tied_logits


def forward(cfg: Phi4FlashConfig, params: dict, cache: dict, tokens, positions, *, slot=None, n_real=None):
    """EVERY layer for tokens ``[B, S]``: a decode step (every slot, S = 1),
    or a chunk of the row at ``slot`` that also runs the cross-decoder on
    each of its tokens (what the skip of :func:`_prefill` is equal to, and
    costs twice). Returns (final-norm hidden, new cache, this call's counts)."""
    x, m, cache, fresh = self_decoder(cfg, params, cache, tokens, positions, slot=slot, n_real=n_real)
    hidden = cross_decoder(cfg, params, cache[f"layer_{cfg.full_layer}"], x, m, positions, slot=slot)
    return hidden, cache, _counts(fresh, cross_tokens=0 if slot is None else n_real)


def _counts(fresh, cross_tokens) -> dict:
    """What one call adds to the counters. The finish's one token a prompt
    is counted where the prompt begins (the chunk at position 0): the head
    program returns no counts."""
    started = jnp.zeros((), jnp.int32) if fresh is None else fresh.astype(jnp.int32)
    return {"prefill_state_resets": started, "prefill_cross_tokens": started + jnp.asarray(cross_tokens, jnp.int32)}


def _prefill(cfg, params, cache, slot, tokens, positions, n_real):
    """A chunk through the self-decoder only. ``hidden`` is what the finish
    needs of a token: the residual entering the full layer, and ``m``."""
    x, m, cache, fresh = self_decoder(cfg, params, cache, tokens, positions, slot=slot, n_real=n_real)
    return {"x": x, "m": m}, cache, _counts(fresh, cross_tokens=0)


def finish(cfg, params, cache, slot, h, position):
    """The end of an admission: the cross-decoder for the prompt's last
    token (``h``: ``x [1, D]`` and ``m [1, d_inner]`` at that token, standing
    at ``position``) against row ``slot`` of the slab, and the head."""
    positions = jnp.reshape(position, (1, 1)).astype(jnp.int32)
    hidden = cross_decoder(
        cfg, params, cache[f"layer_{cfg.full_layer}"], h["x"][:, None], h["m"][:, None], positions, slot=slot
    )
    with jax.named_scope("head"):
        return logits(params, hidden[:, 0])


def _decode(cfg, params, cache, tok, pos):
    hidden, cache, counts = forward(cfg, params, cache, tok, pos)
    with jax.named_scope("head"):
        return logits(params, hidden[:, -1]), cache, counts
