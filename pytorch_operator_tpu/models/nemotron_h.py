"""A hybrid state-space decoder for serving (the ``nemotron_h`` family): the
model is a list of layers of ONE mixer each — a Mamba-2 layer (``M``), an
attention layer (``*``) or an expert layer (``E``) — with per-layer
parameter trees and a per-layer cache of the mixer's own kind.

Reference analog: none (the reference is a training operator). Beside
``models/mimo_v2.py``'s two kinds of key/value cache this family keeps a
third kind of per-slot state: a Mamba-2 layer's, which is CONSTANT in the
sequence's length — the last ``conv_kernel - 1`` inputs of its causal
convolution and the float32 state of its scan — and is a recurrence, not
something a position mask can hide. Three things follow, and they are the
family's contract with the engine (``models/serving.py``):

- a prefill chunk is told how many of its tokens are real (``n_real``):
  beyond the last real token the step size is zero and the convolution's
  tail does not move, so the last chunk's pads leave the state where the
  prompt left it;
- a chunk that stands at position 0 starts its row from ZERO state: the
  slot's last occupant left its own there (``prefill_state_resets`` counts
  those chunks; it equals the engine's ``admitted``);
- rows that hold no request keep stepping at position 0 inside
  ``decode_block``. Their state runs on: it stays finite (the decay
  ``exp(dt A)`` lies in (0, 1) and the input is bounded) and the next
  admission's first chunk discards it.

The layer (``x <- x + mixer(RMSNorm(x))``, residual in the activations'
dtype, no biases but the convolution's, final RMSNorm, untied head):

- ``M``: ``[z | xBC | dt] = x W_in``; a depthwise causal convolution of
  ``conv_kernel`` taps with bias over ``xBC``, then silu; ``[u | B | C]``
  with ``u`` as ``ssm_heads`` heads of ``ssm_head_dim`` and B, C as
  ``ssm_groups`` groups of ``ssm_state`` (head h reads group ``h //
  (heads / groups)``); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``
  a head; ``S_t = exp(dt_t A) S_{t-1} + dt_t u_t (x) B_t``, ``y_t = S_t C_t +
  D u_t``; ``y <- y silu(z)``, RMSNorm over each group's channels with a
  learned scale, ``W_out``. The scan has two forms that agree
  (:func:`scan_chunk`, :func:`scan_step`): a prefill chunk is ONE chunk of
  the chunked scan, entered with the row's state and leaving it behind —
  the carry between chunks is the cache — and a decode step is the one-step
  recurrence over every slot. Which runs is decided by the call's shape.
- ``*``: grouped-query attention with NO position embedding, through a
  full slab and ``ops/cache_attention.py``.
- ``E``: sigmoid-routed experts of the form ``relu(x Wu)^2 Wd`` of which
  this chip holds a share (``parallel/moe.py`` :func:`moe_held`, weights
  times ``routed_scale``), plus a shared expert of the same form, whole on
  every chip and unweighted.

TPU-first shape: everything static; every cache leaf leads with the slot
axis; the scan's state is ``[slots, heads, head_dim, state]`` float32 (the
state axis minor: 128 lanes) and the convolution's tail ``[slots, taps - 1,
channels]`` (channels minor). A chunk writes its keys and values into the
donated slabs where they belong and takes the row's small state out and
puts it back; a decode step updates every row's state elementwise, in
place. Serving only: no training path (the scan has no backward here), no
weight or cache quantisation, no state snapshots.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import layer_list
from .layer_list import logits, rms_norm

Dtype = Any

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
F32 = jnp.float32
# The scan's own products carry or read the float32 state: they run at full
# float32 precision (a fraction of a percent of a layer's operations).
EXACT = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131_072
    d_model: int = 2688
    # attention
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    # Mamba-2
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8
    conv_kernel: int = 4
    # experts
    d_expert: int = 1856
    d_shared: int = 3712
    router_width: int = 128  # experts of the whole layer: what the router scores
    experts_held: tuple[int, int] = (0, 128)  # (first id, count) whose weights live here
    top_k: int = 6
    routed_scale: float = 2.5
    # One mixer a layer: M (Mamba-2), * (attention), E (experts).
    pattern: str = "M"
    rms_eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    # The serving dtype: matrices are MADE in it, a layer at a time (norm
    # scales, the convolution, A_log, dt_bias, D and the selection bias stay
    # float32).
    param_dtype: Dtype = jnp.bfloat16
    decode: bool = False
    max_decode_len: int = 4096

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - {MAMBA, ATTENTION, EXPERTS}:
            raise ValueError(f"pattern {self.pattern!r} is not a string of M, * and E")
        layer_list.check_experts_held(self)
        if self.n_heads % self.n_kv_heads or self.ssm_heads % self.ssm_groups:
            raise ValueError("heads must divide into their key/value heads and groups")

    @property
    def layers(self) -> tuple[str, ...]:
        return tuple(self.pattern)

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

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
            counts=zero_counts(self),
            gauges=cache_bytes,
            derive=functools.partial(layer_list.derived_moe_stats, self),
        )


def make_config(base: dict, over: dict) -> NemotronHConfig:
    """A preset with the server's overrides; this family serves plain
    bfloat16 weights and cache."""
    over = dict(over)
    for knob in ("quantize", "kv_quantize"):
        if over.pop(knob, None):
            raise ValueError(f"the nemotron_h family serves unquantised weights and cache: no {knob}")
    return NemotronHConfig(**{**base, **over})


PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def nemotron3_nano_ep4(**over) -> NemotronHConfig:
    """Nemotron-3-Nano-30B-A3B's published widths as ONE of 4 chips that
    share each layer by expert parallelism: experts 0-31 of the 128 of every
    expert layer here; Mamba, attention, router, shared expert, embedding
    and head whole. Layers 0-15 of the published 52 (``MEMEM*EMEMEM*EME``: 7
    Mamba, 7 expert and 2 attention layers, two whole runs between attention
    layers), the rest lying on further hosts as pipeline stages."""
    return make_config({"experts_held": (0, 32), "pattern": PUBLISHED_PATTERN[:16]}, over)


def nemotron_h_tiny(**over) -> NemotronHConfig:
    """The same structure at test size: every mechanism present (a scan
    state of several heads on fewer groups, a convolution's tail, attention
    without positions on grouped heads, 16 experts top-4 of which 4 are
    held beside a shared one, a scaled routing weight)."""
    base = dict(
        vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        ssm_heads=8, ssm_head_dim=8, ssm_state=16, ssm_groups=2, conv_kernel=4,
        d_expert=32, d_shared=48, router_width=16, experts_held=(0, 4), top_k=4,
        pattern="MEM*EME", dtype=jnp.float32, param_dtype=jnp.float32, max_decode_len=128,
    )
    return make_config(base, over)


# Presets by the name a job's ``--config`` gives (models/serving.py).
CONFIGS = {
    "nemotron3-nano-ep4": "nemotron3_nano_ep4",
    "nemotron-h-tiny": "nemotron_h_tiny",
}


# ---- parameters: made in the serving dtype, a layer at a time ----


def layer_shapes(cfg: NemotronHConfig, kind: str) -> dict:
    """``path -> (shape, fan_in, dtype)`` of one layer's leaves, as
    ``layer_list.draw`` reads them. ``A_log``, ``dt_bias`` and ``D`` are
    made in :func:`init_layer`."""
    D, w = cfg.d_model, cfg.param_dtype
    out = {("norm", "scale"): ((D,), None, F32)}
    if kind == MAMBA:
        di, C, K = cfg.d_inner, cfg.conv_channels, cfg.conv_kernel
        out.update({
            ("ssm", "in_proj"): ((D, di + C + cfg.ssm_heads), D, w),
            ("ssm", "conv_w"): ((K, C), K, F32),
            ("ssm", "conv_b"): ((C,), K, F32),
            ("ssm", "norm_scale"): ((di,), None, F32),
            ("ssm", "out_proj"): ((di, D), di, w),
        })
    elif kind == ATTENTION:
        H, Hk, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        out.update({
            ("attn", "q_proj"): ((D, H, d), D, w),
            ("attn", "k_proj"): ((D, Hk, d), D, w),
            ("attn", "v_proj"): ((D, Hk, d), D, w),
            ("attn", "o_proj"): ((H * d, D), H * d, w),
        })
    else:
        n, F, Fs, E = cfg.experts_held[1], cfg.d_expert, cfg.d_shared, cfg.router_width
        out.update({
            ("moe", "router"): ((D, E), D, w),
            ("moe", "e_bias"): ((E,), 0, F32),
            ("moe", "w_up"): ((n, D, F), D, w),
            ("moe", "w_down"): ((n, F, D), F, w),
            ("shared", "up_proj"): ((D, Fs), D, w),
            ("shared", "down_proj"): ((Fs, D), Fs, w),
        })
    return out


def init_layer(cfg: NemotronHConfig, kind: str, key, layer) -> dict:
    """Layer ``layer``'s leaves (``layer`` may be traced: layers of one kind
    share a compiled program). A Mamba layer's ``A_log`` is log U[1, 16],
    its ``dt_bias`` the inverse softplus of a step log-uniform in [1e-3,
    1e-1], its ``D`` ones: the published initialisation."""
    key = jax.random.fold_in(key, layer)
    tree = layer_list.draw(key, layer_shapes(cfg, kind))
    if kind == MAMBA:
        H = cfg.ssm_heads
        ka, kd = jax.random.split(jax.random.fold_in(key, 1 << 10))
        dt = jnp.exp(jax.random.uniform(kd, (H,), F32, jnp.log(1e-3), jnp.log(1e-1)))
        tree["ssm"].update(
            A_log=jnp.log(jax.random.uniform(ka, (H,), F32, 1.0, 16.0)),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            D=jnp.ones((H,), F32),
        )
    return tree


def init_outer(cfg: NemotronHConfig, key) -> dict:
    return layer_list.draw(jax.random.fold_in(key, 1 << 20), layer_list.outer_shapes(cfg))


def init_params(cfg: NemotronHConfig, key) -> dict:
    """The serving tree, a layer at a time (``layer_list.init_params``).
    ``init_layer`` and ``init_outer`` are looked up at call time, so a
    caller that brings its own seeded leaves (the benchmark) replaces those
    two."""
    return layer_list.init_params(cfg, key, cfg.layers, init_outer, init_layer)


# ---- the cache: one state per layer that keeps one, of the mixer's kind ----


def init_cache(cfg: NemotronHConfig, slots: int, chunk: int) -> dict:
    """``layer_i -> {k, v}`` for an attention layer (``max_decode_len``
    positions), ``{conv, state}`` for a Mamba layer (the last ``conv_kernel
    - 1`` inputs of its convolution, and its scan's float32 state); an
    expert layer keeps nothing. Every leaf leads with the slot axis."""
    cache = {}
    for i, kind in enumerate(cfg.layers):
        if kind == ATTENTION:
            slab = (slots, cfg.n_kv_heads, cfg.max_decode_len, cfg.head_dim)
            cache[f"layer_{i}"] = {"k": jnp.zeros(slab, cfg.dtype), "v": jnp.zeros(slab, cfg.dtype)}
        elif kind == MAMBA:
            cache[f"layer_{i}"] = {
                "conv": jnp.zeros((slots, cfg.conv_kernel - 1, cfg.conv_channels), cfg.dtype),
                "state": jnp.zeros((slots, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), F32),
            }
    return cache


cache_bytes = layer_list.state_cache_bytes


def zero_counts(cfg: NemotronHConfig) -> dict:
    """The expert layers' counters, and the chunks that started a row from
    zero state."""
    return {**layer_list.zero_moe_counts(cfg), "prefill_state_resets": jnp.zeros((), jnp.int32)}


# ---- the scan, in two forms that agree ----


def scan_step(u, Bm, Cm, dt, A, state):
    """The one-step recurrence over rows: ``u [B, H, P]``, ``Bm``/``Cm [B,
    G, N]``, ``dt [B, H]`` (softplus applied), ``A [H]``, ``state [B, H, P,
    N]``; all float32. Returns ``(y [B, H, P], new state)``: ``S' = exp(dt
    A) S + dt u (x) B``, ``y = S' C``. One elementwise pass over the state
    and a sum over its minor axis: what a row costs is its state read and
    written once."""
    B, H, P, N = state.shape
    G = Bm.shape[1]
    S = state.reshape(B, G, H // G, P, N)
    decay = jnp.exp(dt * A).reshape(B, G, H // G, 1, 1)
    du = (dt[:, :, None] * u).reshape(B, G, H // G, P, 1)
    S = decay * S + du * Bm[:, :, None, None, :]
    y = jnp.sum(S * Cm[:, :, None, None, :], axis=-1)
    return y.reshape(B, H, P), S.reshape(B, H, P, N)


def scan_chunk(u, Bm, Cm, dt, A, state):
    """One chunk of the chunked scan for one row: ``u [S, H, P]``,
    ``Bm``/``Cm [S, G, N]``, ``dt [S, H]`` (softplus applied; zero where the
    state must not move), ``A [H]``, entry ``state [H, P, N]``; all float32.
    Returns ``(y [S, H, P], exit state)``, equal to ``S`` calls of
    :func:`scan_step`: with ``cum_t = sum_{r <= t} dt_r A``, inside the chunk
    ``y_t = sum_{s <= t} exp(cum_t - cum_s) (C_t . B_s) dt_s u_s`` (the
    decay-masked ``C B^T`` product), the entry state adds ``exp(cum_t) S_0
    C_t``, and the exit state is ``exp(cum_last) S_0 + sum_s exp(cum_last -
    cum_s) dt_s u_s (x) B_s``."""
    S, H, P = u.shape
    G, N = Bm.shape[1:]
    Hg = H // G
    cum = jnp.cumsum(dt * A, axis=0)  # [S, H], non-increasing
    t, s = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    # exp of a masked exponent: above the diagonal the difference is positive and may overflow.
    span = jnp.where((s <= t)[None], cum.T[:, :, None] - cum.T[:, None, :], -jnp.inf)
    decay = jnp.exp(span).reshape(G, Hg, S, S)  # [g, h, t, s]
    cb = jnp.einsum("tgn,sgn->gts", Cm, Bm, precision=EXACT)
    du = (dt[:, :, None] * u).reshape(S, G, Hg, P)
    y = jnp.einsum("ghts,sghp->tghp", decay * cb[:, None], du, precision=EXACT)
    entry = state.reshape(G, Hg, P, N)
    y = y + jnp.exp(cum).reshape(S, G, Hg, 1) * jnp.einsum("tgn,ghpn->tghp", Cm, entry, precision=EXACT)
    left = jnp.exp(cum[-1][None, :] - cum).reshape(S, G, Hg, 1)  # decay from s to the chunk's end
    exit_ = jnp.exp(cum[-1]).reshape(G, Hg, 1, 1) * entry + jnp.einsum(
        "sghp,sgn->ghpn", left * du, Bm, precision=EXACT
    )
    return y.reshape(S, H, P), exit_.reshape(H, P, N)


# ---- the mixers ----


def _causal_conv(cfg, w: dict, tail, xBC):
    """The depthwise causal convolution over ``xBC [B, S, C]`` entered with
    ``tail [B, K - 1, C]``, the inputs that came before: float32 ``silu(b +
    sum_j w_j x_{t-K+1+j})``, and the window ``[B, K - 1 + S, C]`` it ran
    over (the new tail is cut from it)."""
    K, S = cfg.conv_kernel, xBC.shape[1]
    window = jnp.concatenate([tail, xBC], axis=1)
    taps = window.astype(F32)
    conv = w["conv_b"] + sum(w["conv_w"][j] * taps[:, j : j + S] for j in range(K))
    return jax.nn.silu(conv), window


def ssm_mixer(cfg: NemotronHConfig, w: dict, cache: dict, x, *, slot=None, fresh=None, n_real=None):
    """A Mamba-2 layer for ``x [B, S, D]``. With ``slot`` (a prefill chunk:
    ``B == 1``) the row's state is cut out of ``cache``'s leaves, zeroed
    where ``fresh`` (the chunk stands at position 0), run through ONE chunk
    of the scan in which only the first ``n_real`` tokens move it, and put
    back; without (a decode step: ``S == 1``) every row takes one step of
    the recurrence. Returns ``(out [B, S, D], new cache)``."""
    B, S, _ = x.shape
    H, P, G, N, K = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state, cfg.conv_kernel
    di, C = cfg.d_inner, cfg.conv_channels
    proj = x @ w["in_proj"]
    z, xBC, dt = proj[..., :di], proj[..., di : di + C], proj[..., di + C :]
    dt = jax.nn.softplus(dt.astype(F32) + w["dt_bias"])  # [B, S, H]
    A = -jnp.exp(w["A_log"])
    if slot is None:
        tail, state = cache["conv"], cache["state"]
    else:
        # Whatever the slot's last occupant (or a parked row's idle steps) left there is dropped, not multiplied away.
        start = lambda leaf: jnp.where(fresh, jnp.zeros_like(leaf), leaf)
        tail = start(jax.lax.dynamic_slice_in_dim(cache["conv"], slot, 1, 0))
        state = start(jax.lax.dynamic_slice_in_dim(cache["state"], slot, 1, 0)[0])
    with jax.named_scope("ssm_conv"):
        conv, window = _causal_conv(cfg, w, tail, xBC)
    u = conv[..., :di].reshape(B, S, H, P)
    Bm = conv[..., di : di + G * N].reshape(B, S, G, N)
    Cm = conv[..., di + G * N :].reshape(B, S, G, N)
    with jax.named_scope("ssm_scan"):
        if slot is None:
            y, state = scan_step(u[:, 0], Bm[:, 0], Cm[:, 0], dt[:, 0], A, state)
            y, tail = y[:, None], window[:, 1:]
            new = {"conv": tail, "state": state}
        else:
            real = jnp.arange(S) < n_real
            y, state = scan_chunk(u[0], Bm[0], Cm[0], jnp.where(real[:, None], dt[0], 0.0), A, state)
            # The inputs before the first token that is not real: what the next chunk, or the first decode step, convolves with.
            tail = jax.lax.dynamic_slice_in_dim(window, n_real, K - 1, 1)
            new = {
                "conv": jax.lax.dynamic_update_slice_in_dim(cache["conv"], tail, slot, 0),
                "state": jax.lax.dynamic_update_slice_in_dim(cache["state"], state[None], slot, 0),
            }
            y = y[None]
    y = y + w["D"][:, None] * u
    # Gate, then RMSNorm over each group's channels.
    y = (y.reshape(B, S, di) * jax.nn.silu(z.astype(F32))).reshape(B, S, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_eps)
    y = (y.reshape(B, S, di) * w["norm_scale"]).astype(x.dtype)
    return y @ w["out_proj"], new


def attention(cfg: NemotronHConfig, w: dict, cache: dict, x, positions, *, slot=None):
    """Grouped-query attention with NO position embedding, through the
    layer's full slab: ``layer_list.slab_attention``, which reads the heads
    off the leaves. Returns (out, new cache)."""
    return layer_list.slab_attention(w, cache, x, positions, dtype=cfg.dtype, slot=slot)


def shared_expert(w: dict, x):
    """``relu(x Wu)^2 Wd``, the square in float32 between the products."""
    up = jnp.dot(x, w["up_proj"], preferred_element_type=F32)
    return jnp.square(jax.nn.relu(up)).astype(x.dtype) @ w["down_proj"]


def forward(cfg: NemotronHConfig, params: dict, cache: dict, tokens, positions, *, slot=None, n_real=None):
    """Tokens ``[B, S]`` at ``positions [B, S]`` through every layer and its
    cache: a prefill chunk (one row at ``slot``, S = chunk, the first
    ``n_real`` tokens real) or a decode step (every slot, S = 1, each row at
    its own position). Returns (final-norm hidden [B, S, D], new cache, this
    call's counts)."""
    from ..parallel.moe import RELU2, moe_held

    B, S = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
    moe_counts = layer_list.zero_moe_counts(cfg)
    # A chunk that stands at position 0 starts its row from zero state.
    fresh = None if slot is None else positions[0, 0] == 0
    new_cache = {}
    for i, kind in enumerate(cfg.layers):
        w, name = params["layers"][i], f"layer_{i}"
        h = rms_norm(x, w["norm"]["scale"], cfg.rms_eps)
        if kind == MAMBA:
            with jax.named_scope("ssm"):
                y, new_cache[name] = ssm_mixer(
                    cfg, w["ssm"], cache[name], h, slot=slot, fresh=fresh, n_real=n_real
                )
        elif kind == ATTENTION:
            with jax.named_scope("attn_full"):
                y, new_cache[name] = attention(cfg, w["attn"], cache[name], h, positions, slot=slot)
        else:
            with jax.named_scope("moe"):
                flat = h.reshape(B * S, cfg.d_model)
                y, c = moe_held(
                    w["moe"], flat, top_k=cfg.top_k, experts_held=cfg.experts_held,
                    form=RELU2, weight_scale=cfg.routed_scale,
                )
                with jax.named_scope("moe_shared"):
                    y = (y + shared_expert(w["shared"], flat)).reshape(B, S, cfg.d_model)
            moe_counts = jax.tree.map(jnp.add, moe_counts, c)
        x = x + y.astype(x.dtype)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
    resets = jnp.zeros((), jnp.int32) if fresh is None else fresh.astype(jnp.int32)
    return x, new_cache, {**moe_counts, "prefill_state_resets": resets}


def _prefill(cfg, params, cache, slot, tokens, positions, n_real):
    return forward(cfg, params, cache, tokens, positions, slot=slot, n_real=n_real)


def _decode(cfg, params, cache, tok, pos):
    hidden, cache, counts = forward(cfg, params, cache, tok, pos)
    with jax.named_scope("head"):
        return logits(params, hidden[:, -1]), cache, counts
