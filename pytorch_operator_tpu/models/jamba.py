"""A Mamba-1 / attention hybrid for serving (the ``jamba`` family): a list of
layers, each ``x <- x + mixer(RMSNorm(x)); x <- x + FF(RMSNorm(x))``, whose
mixer is a Mamba-1 layer or, once a period, grouped-query attention with NO
position embedding; ``FF`` is SwiGLU without bias in every layer; final
RMSNorm; the head is the embedding.

Reference analog: none (the reference is a training operator). Beside
``models/nemotron_h.py`` (Mamba-2, experts) and ``models/phi4_flash.py`` (a
decoder-hybrid-decoder) this family is the plain pairing constant state is
sold for: a few attention layers whose slabs grow with the context (here ONE
key/value head under 20 query heads: 1 KB a position over both layers)
beside many state-space layers whose state does not (9.3 MB a row), at slabs
of tens of thousands of positions.

- layer ``i`` attends iff ``(i - attn_offset) % attn_period == 0``, else it is
  Mamba-1: the tree's one Mamba-1 mixer (``models/ssm.py``), with the three
  inner RMSNorms of the ``jamba`` model class (over ``dt``, ``B`` and ``C``
  as ``x_proj`` gives them);
- attention: ``n_heads`` queries on ``n_kv_heads`` key/value heads of
  ``d_model / n_heads``, causal softmax at ``head_dim ^ -1/2``, no bias, no
  window, through a full slab and ``ops/cache_attention.py``
  (``layer_list.slab_attention``, which ``models/nemotron_h.py`` runs too);
- the family's contract with the engine is the hybrid families' (a chunk is
  told its ``n_real`` and starts its row from ZERO state at position 0:
  ``prefill_state_resets`` = ``admitted``) and one thing more: **a decode
  step leaves a row whose position is negative untouched**
  (``ServingModel.holds``): its scan state and convolution tail do not move,
  and its keys and values go to the parking position ``max_decode_len - 1``
  that no live stream attends. That is what lets the engine stop a long
  prompt's prefill at a chunk's end and go on at the next boundary while the
  other rows decode (``serving/engine.py``): the row's state, tail and slabs
  live in its slot meanwhile.

TPU-first shape: everything static; every cache leaf leads with the slot
axis; the slabs ``[slots, 1, max_decode_len, 128]`` (128 lanes minor).
Serving only: no training path (the scan has no backward here), no weight
or cache quantisation, no state snapshots.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import layer_list, ssm
from .layer_list import rms_norm, tied_logits as logits

Dtype = Any

MAMBA, FULL = "mamba", "attn_full"
F32 = jnp.float32
# Standard deviation of the inner norms' seeded scales around 1 (exactly 1
# would leave them untested).
INNER_NORM_STD = 0.1


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65_536
    d_model: int = 2560
    n_layers: int = 28
    n_heads: int = 20
    n_kv_heads: int = 1
    d_ff: int = 8192
    # layer i attends iff (i - attn_offset) % attn_period == 0
    attn_offset: int = 7
    attn_period: int = 14
    # Mamba-1
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    rms_eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16
    # The serving dtype: matrices are MADE in it, a layer at a time (norm
    # scales, the convolution, A_log, dt_bias and D stay float32).
    param_dtype: Dtype = jnp.bfloat16
    decode: bool = False
    max_decode_len: int = 4096

    def __post_init__(self):
        if self.d_model % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError("heads must divide the width, and key/value heads the heads")
        if not 0 <= self.attn_offset < self.attn_period:
            raise ValueError(f"attn_offset {self.attn_offset} outside the period {self.attn_period}")

    @property
    def layers(self) -> tuple[str, ...]:
        return tuple(FULL if (i - self.attn_offset) % self.attn_period == 0 else MAMBA for i in range(self.n_layers))

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

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
            holds=True,
            counts=zero_counts(),
            gauges=layer_list.state_cache_bytes,
        )


def make_config(base: dict, over: dict) -> JambaConfig:
    """A preset with the server's overrides; this family serves plain
    bfloat16 weights and cache."""
    over = dict(over)
    for knob in ("quantize", "kv_quantize"):
        if over.pop(knob, None):
            raise ValueError(f"the jamba family serves unquantised weights and cache: no {knob}")
    return JambaConfig(**{**base, **over})


def jamba2_3b(**over) -> JambaConfig:
    """AI21-Jamba2-3B as published, whole on one chip: 28 layers (26 Mamba-1,
    attention at layers 7 and 21), 3.03 B parameters."""
    return make_config({}, over)


def jamba_tiny(**over) -> JambaConfig:
    """The same structure at test size: two attention layers of ONE key/value
    head under several queries between Mamba-1 layers with inner norms."""
    base = dict(
        vocab_size=256, d_model=64, n_layers=6, n_heads=4, n_kv_heads=1, d_ff=128, attn_offset=1, attn_period=3,
        d_state=8, dt_rank=4, dtype=jnp.float32, param_dtype=jnp.float32, max_decode_len=128,
    )
    return make_config(base, over)


# Presets by the name a job's ``--config`` gives (models/serving.py).
CONFIGS = {
    "jamba2-3b": "jamba2_3b",
    "jamba-tiny": "jamba_tiny",
}


# ---- parameters: made in the serving dtype, a layer at a time ----


def layer_shapes(cfg: JambaConfig, kind: str) -> dict:
    """``path -> (shape, fan_in, dtype)`` of one layer's leaves that are
    plain draws, as ``layer_list.draw`` reads them. A matrix that writes to
    the residual stream is drawn ``1 / sqrt(2 n_layers)`` smaller. The
    Mamba mixer's ``A_log``, ``dt_bias``, ``dt_proj``, ``D`` and inner norm
    scales are made in :func:`init_layer`."""
    D, F, w = cfg.d_model, cfg.d_ff, cfg.param_dtype
    res = 2 * cfg.n_layers
    out = {
        ("norm1", "scale"): ((D,), None, F32), ("norm2", "scale"): ((D,), None, F32),
        ("mlp", "gate_up"): ((D, 2 * F), D, w), ("mlp", "down"): ((F, D), F * res, w),
    }
    if kind == MAMBA:
        shapes = ssm.mixer_shapes(D, cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank, w, cfg.d_inner * res)
        out.update({("ssm", name): leaf for name, leaf in shapes.items()})
    else:
        H, Hk, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        out.update({
            ("attn", "q_proj"): ((D, H, d), D, w),
            ("attn", "k_proj"): ((D, Hk, d), D, w),
            ("attn", "v_proj"): ((D, Hk, d), D, w),
            ("attn", "o_proj"): ((H * d, D), H * d * res, w),
        })
    return out


def init_layer(cfg: JambaConfig, kind: str, key, layer) -> dict:
    """Layer ``layer``'s leaves (``layer`` may be traced: layers of one kind
    share a compiled program). A Mamba layer gets the published Mamba
    initialisation (``ssm.published_init``) and inner norm scales drawn near
    1."""
    key = jax.random.fold_in(key, layer)
    tree = layer_list.draw(key, layer_shapes(cfg, kind))
    if kind == MAMBA:
        tree["ssm"].update(ssm.published_init(jax.random.fold_in(key, 1 << 10), cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.param_dtype))
        for j, (name, n) in enumerate(zip(ssm.INNER_NORMS, (cfg.dt_rank, cfg.d_state, cfg.d_state))):
            tree["ssm"][name] = 1.0 + INNER_NORM_STD * jax.random.normal(jax.random.fold_in(key, (1 << 11) + j), (n,), F32)
    return tree


def outer_shapes(cfg: JambaConfig) -> dict:
    """The embedding, which is also the head, and the final norm."""
    D = cfg.d_model
    return {("embed", "embedding"): ((cfg.vocab_size, D), D, cfg.param_dtype), ("final_norm", "scale"): ((D,), None, F32)}


def init_outer(cfg: JambaConfig, key) -> dict:
    return layer_list.draw(jax.random.fold_in(key, 1 << 20), outer_shapes(cfg))


def init_params(cfg: JambaConfig, key) -> dict:
    """The serving tree, a layer at a time (``layer_list.init_params``).
    ``init_layer`` and ``init_outer`` are looked up at call time, so a
    caller that brings its own seeded leaves (the benchmark) replaces those
    two."""
    return layer_list.init_params(cfg, key, cfg.layers, init_outer, init_layer)


# ---- the cache: a slab for an attention layer, constant state for a Mamba layer ----


def init_cache(cfg: JambaConfig, slots: int, chunk: int) -> dict:
    """``layer_i -> {k, v}`` for an attention layer (``max_decode_len``
    positions of ``n_kv_heads`` heads), ``{conv, state}`` for a Mamba layer
    (``ssm.init_state``). Every leaf leads with the slot axis. A slot
    reserves its whole slab: at the published sizes 2 x 2 x 32,768 x 128 x 2 B
    = 33.5 MB beside 9.3 MB of state."""
    slab = (slots, cfg.n_kv_heads, cfg.max_decode_len, cfg.head_dim)
    cache = {}
    for i, kind in enumerate(cfg.layers):
        if kind == FULL:
            cache[f"layer_{i}"] = {"k": jnp.zeros(slab, cfg.dtype), "v": jnp.zeros(slab, cfg.dtype)}
        else:
            cache[f"layer_{i}"] = ssm.init_state(slots, cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dtype)
    return cache


def zero_counts() -> dict:
    """The chunks that started a row from zero state."""
    return {"prefill_state_resets": jnp.zeros((), jnp.int32)}


# ---- the forward ----


def forward(cfg: JambaConfig, params: dict, cache: dict, tokens, positions, *, slot=None, n_real=None):
    """Tokens ``[B, S]`` at ``positions [B, S]`` through every layer and its
    cache: a prefill chunk (one row at ``slot``, S = chunk, the first
    ``n_real`` tokens real) or a decode step (every slot, S = 1, each row at
    its own position; a row at a NEGATIVE position is held: no leaf of it
    moves). Returns (final-norm hidden [B, S, D], new cache, this call's
    counts)."""
    with jax.named_scope("embed"):
        x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
    # A chunk that stands at position 0 starts its row from zero state.
    fresh = None if slot is None else positions[0, 0] == 0
    hold = positions[:, 0] < 0 if slot is None else None
    new_cache = {}
    for i, kind in enumerate(cfg.layers):
        w, name = params["layers"][i], f"layer_{i}"
        h = rms_norm(x, w["norm1"]["scale"], cfg.rms_eps)
        if kind == MAMBA:
            with jax.named_scope("ssm"):
                y, _, new_cache[name] = ssm.mamba1_mixer(
                    w["ssm"], cache[name], h, slot=slot, fresh=fresh, n_real=n_real, hold=hold, norm_eps=cfg.rms_eps
                )
        else:
            with jax.named_scope("attn_full"):
                y, new_cache[name] = layer_list.slab_attention(
                    w["attn"], cache[name], h, positions, dtype=cfg.dtype, slot=slot, hold=hold
                )
        x = x + y.astype(x.dtype)
        with jax.named_scope("dense_mlp"):
            x = x + layer_list.gated_mlp(w["mlp"], rms_norm(x, w["norm2"]["scale"], cfg.rms_eps))
    x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
    resets = jnp.zeros((), jnp.int32) if fresh is None else fresh.astype(jnp.int32)
    return x, new_cache, {"prefill_state_resets": resets}


def _prefill(cfg, params, cache, slot, tokens, positions, n_real):
    return forward(cfg, params, cache, tokens, positions, slot=slot, n_real=n_real)


def _decode(cfg, params, cache, tok, pos):
    hidden, cache, counts = forward(cfg, params, cache, tok, pos)
    with jax.named_scope("head"):
        return logits(params, hidden[:, -1]), cache, counts
