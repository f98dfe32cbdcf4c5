"""The ONE Mamba-1 mixer of the tree: what ``models/phi4_flash.py`` and
``models/jamba.py`` both run for a state-space layer (``models/nemotron_h.py``
keeps its own: Mamba-2's decay is one scalar a head, and its chunk form is a
decay-masked product that does not apply here).

The equations, for ``x [B, S, D]``: ``[u | z] = x W_in``; a depthwise causal
convolution of ``d_conv`` taps with bias over ``u``, then ``silu``; ``[delta |
B | C] = u W_x``; **where the layer has them, an RMSNorm with a learned scale
over each of the three** (``dt_norm``, ``b_norm``, ``c_norm``: the ``jamba``
model class's ``dt_layernorm``, ``b_layernorm``, ``c_layernorm``); ``dt =
softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``; ``S_t[n, c] = exp(dt_t[c]
A[n, c]) S_{t-1}[n, c] + dt_t[c] B_t[n] u_t[c]``, ``y_t[c] = sum_n S_t[n, c]
C_t[n] + D[c] u_t[c]``; ``out = (y * silu(z)) W_out``. The decay is per channel
AND per state index.

The mixer names no model and takes no config: every size is a leaf's shape
(``d_inner`` = ``in_proj``'s columns / 2, ``d_conv`` = ``conv_w``'s taps,
``dt_rank`` = ``dt_proj``'s rows, ``d_state`` = ``A_log``'s rows), and the
inner norms are there iff the layer's tree holds their scales.

Its contract with the engine (``models/serving.py``): a chunk freezes the
state beyond its ``n_real`` tokens and starts a row from ZERO state where
``fresh`` (the chunk stands at position 0); a chunk that is not fresh enters
with what the row's last chunk left, WHENEVER that chunk ran: the state, the
convolution's tail and the slabs live in the slot between a prompt's chunks,
which is what lets the engine spread a long prompt over several boundaries
(``serving/engine.py``). Parked rows' state runs on and is discarded.

TPU-first shape: the scan state is ``[slots, d_state, d_inner]``, channels
minor (``d_state`` = 16 minor would be padded to 128 lanes, 8 times its
bytes), float32; the convolution's tail ``[slots, d_conv - 1, d_inner]`` in
the activations' dtype. Serving only: the scan has no backward here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
# The chunk's scan (:func:`scan_chunk`): the tokens an iteration of the
# kernel's loop takes (a float32 tile's 8 sublanes: ``u``, ``dt`` and ``y`` are
# read and written a whole tile at a time), and the most tokens and channels
# of a grid step. The step's ``u``, ``dt`` and ``y`` blocks lie in fast memory
# twice over: 8 MB here, inside the 16 MiB a kernel may use unasked (the
# compiler fuses the state's write-back into the call, and the fused call
# does not carry a larger request: PERF.md section 6, PR 46).
TOKENS = 8
TILE = (256, 1280)
# The inner norms' scales, by the part of ``x_proj``'s output each is over.
INNER_NORMS = ("dt_norm", "b_norm", "c_norm")


# ---- the scan, in two forms that agree ----


def scan_step(u, Bm, Cm, dt, A, state):
    """The one-step recurrence over rows: ``u``, ``dt [B, C]`` (softplus
    applied), ``Bm``/``Cm [B, N]``, ``A [N, C]``, ``state [B, N, C]``; all
    float32. Returns ``(y [B, C], new state)``: ``S' = exp(dt A) S + dt u (x)
    B``, ``y = sum_n S' C``. One elementwise pass over the state and a sum
    over its state axis: what a row costs is its state read and written once."""
    S = jnp.exp(dt[:, None, :] * A) * state + (dt * u)[:, None, :] * Bm[:, :, None]
    return jnp.sum(S * Cm[:, :, None], axis=1), S


def scan_chunk(u, Bm, Cm, dt, A, state):
    """A chunk of one row from its entry state: ``u``, ``dt [S, C]`` (softplus
    applied; zero where the state must not move), ``Bm``/``Cm [S, N]``, ``A
    [N, C]``, entry ``state [N, C]``; all float32. Returns ``(y [S, C], exit
    state)``: :func:`scan_step` a token, as ONE Pallas kernel a layer's chunk,
    named ``ssm_scan_chunk`` in a trace, over a grid of (channel block, token
    block) of at most :data:`TILE`: a block's state (``[16, 1280]``: 20 vector
    registers) is the carry of a loop over the token block's tokens,
    :data:`TOKENS` an iteration, and lies in the output's block, in fast
    memory, between a channel block's grid steps; ``u``, ``dt`` and ``y`` move
    as ``[tokens, channels]`` tiles. A chunk that is no whole number of token
    blocks is padded with steps whose ``dt`` is 0. The recurrence as a
    ``lax.scan`` unrolled 8 (what this was until PR 46) is the same arithmetic
    but one fused operation a token a layer on the device, 13,312 a chunk of
    512 through 26 layers: a 4 s trace of prefill held 1.5 M of them, and a
    traced run's reductions, paid by the operation, outlasted the run's time
    limit; on the v5e the kernel takes 0.157 ms a chunk of 512 x 5120 where
    the loop took 0.268, to the last bit the same values (PERF.md section 6,
    PR 46, has the table by tile). Sub-chunks by an
    associative scan over (decay, input) pairs, built first, cost 4-8 times
    the loop: every level writes and reads the chunk's ``[S, N, C]`` states
    through device memory (PERF.md section 6, PR 36). On a TPU it is a Mosaic
    kernel; where the default backend is the CPU, the same kernel under the
    interpreter (as ops/cache_attention.py decides)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, C = u.shape
    N = A.shape[0]
    tb = min(TILE[0], S + -S % TOKENS)
    cb = next((b for b in range(TILE[1], 0, -128) if C % b == 0), C)
    pad = -S % tb  # a step whose dt is 0 leaves the state as it is
    if pad:
        u, Bm, Cm, dt = (jnp.pad(a, ((0, pad), (0, 0))) for a in (u, Bm, Cm, dt))
    T = S + pad

    def kernel(u_ref, b_ref, c_ref, dt_ref, a_ref, s_ref, y_ref, out_ref):
        """``tb`` tokens of ``cb`` channels: the state comes from, and goes
        back to, the output's block, which stays in fast memory over a
        channel block's grid steps."""
        A = a_ref[...]
        eye = jax.lax.broadcasted_iota(jnp.int32, (N, N), 0) == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)
        # Token k's B (or C) ``[1, N]`` stood up as ``[N, 1]``, to go beside the state's rows.
        column = lambda rows, k: jnp.sum(jnp.where(eye, rows[k : k + 1, :], 0.0), axis=1, keepdims=True)  # noqa: E731

        @pl.when(pl.program_id(1) == 0)
        def _():
            out_ref[...] = s_ref[...]

        def tokens(i, state):
            at = pl.ds(pl.multiple_of(i * TOKENS, TOKENS), TOKENS)
            dts, Bs, Cs = dt_ref[at, :], b_ref[at, :], c_ref[at, :]
            xs = dts * u_ref[at, :]
            ys = []
            for k in range(TOKENS):
                state = jnp.exp(dts[k : k + 1, :] * A) * state + xs[k : k + 1, :] * column(Bs, k)
                ys.append(jnp.sum(state * column(Cs, k), axis=0, keepdims=True))
            y_ref[at, :] = jnp.concatenate(ys, axis=0)
            return state

        out_ref[...] = jax.lax.fori_loop(0, tb // TOKENS, tokens, out_ref[...])

    tile = pl.BlockSpec((tb, cb), lambda j, i: (i, j))
    low = pl.BlockSpec((tb, N), lambda j, i: (i, 0))
    rows = pl.BlockSpec((N, cb), lambda j, i: (0, j))
    y, state = pl.pallas_call(
        kernel,
        grid=(C // cb, T // tb),
        in_specs=[tile, low, low, tile, rows, rows],
        out_specs=[tile, rows],
        out_shape=[jax.ShapeDtypeStruct((T, C), F32), jax.ShapeDtypeStruct((N, C), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=jax.default_backend() == "cpu",
        name="ssm_scan_chunk",
    )(u, Bm, Cm, dt, A, state)
    return y[:S], state


# ---- the mixer ----


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def mamba1_mixer(w: dict, cache: dict, x, *, slot=None, fresh=None, n_real=None, hold=None, norm_eps: float = 1e-6):
    """A Mamba-1 layer for ``x [B, S, D]`` with the leaves ``w`` (``in_proj``,
    ``conv_w``, ``conv_b``, ``x_proj``, ``dt_proj``, ``dt_bias``, ``A_log``,
    ``D``, ``out_proj`` and, optionally, the three of :data:`INNER_NORMS`,
    each applied at ``norm_eps``) and the layer's cache ``{conv, state}``.
    With ``slot`` (a prefill chunk: ``B == 1``) the row's state is cut out of
    ``cache``'s leaves, zeroed where ``fresh``, run through the chunk form of
    the scan in which only the first ``n_real`` tokens move it, and put back;
    without (a decode step: ``S == 1``) every row takes one step of the
    recurrence, but for the rows that ``hold [B]`` (bool; None = none does),
    whose state and tail stay as they are: a row part-way through its prompt
    (``ServingModel.holds``). Returns ``(out [B, S, D], m [B, S, d_inner],
    new cache)``; ``m`` is the scan's output with the ``D`` term, before the
    gate."""
    B, S, _ = x.shape
    di, N, K, R = w["in_proj"].shape[1] // 2, w["A_log"].shape[0], w["conv_w"].shape[0], w["dt_proj"].shape[0]
    proj = x @ w["in_proj"]
    u, z = proj[..., :di], proj[..., di:]
    if slot is None:
        tail, state = cache["conv"], cache["state"]
    else:
        # Whatever the slot's last occupant (or a parked row's idle steps) left there is dropped, not multiplied away.
        start = lambda leaf: jnp.where(fresh, jnp.zeros_like(leaf), leaf)
        tail = start(jax.lax.dynamic_slice_in_dim(cache["conv"], slot, 1, 0))
        state = start(jax.lax.dynamic_slice_in_dim(cache["state"], slot, 1, 0)[0])
    with jax.named_scope("ssm_conv"):
        window = jnp.concatenate([tail, u], axis=1)  # [B, K - 1 + S, di]
        taps = window.astype(F32)
        u = jax.nn.silu(w["conv_b"] + sum(w["conv_w"][j] * taps[:, j : j + S] for j in range(K)))
    low = jnp.dot(u.astype(x.dtype), w["x_proj"], preferred_element_type=F32)
    delta, Bm, Cm = low[..., :R], low[..., R : R + N], low[..., R + N :]
    if INNER_NORMS[0] in w:
        delta, Bm, Cm = (_rms(part, w[name], norm_eps) for part, name in zip((delta, Bm, Cm), INNER_NORMS))
    dt = jnp.dot(delta.astype(x.dtype), w["dt_proj"], preferred_element_type=F32)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # [B, S, di]
    A = -jnp.exp(w["A_log"])
    with jax.named_scope("ssm_scan"):
        if slot is None:
            y, moved = scan_step(u[:, 0], Bm[:, 0], Cm[:, 0], dt[:, 0], A, state)
            y = y[:, None]
            new = {"conv": window[:, 1:], "state": moved}
            if hold is not None:  # a select inside the state's one elementwise pass
                new = {"conv": jnp.where(hold[:, None, None], tail, new["conv"]),
                       "state": jnp.where(hold[:, None, None], state, moved)}
        else:
            real = jnp.arange(S) < n_real
            y, state = scan_chunk(u[0], Bm[0], Cm[0], jnp.where(real[:, None], dt[0], 0.0), A, state)
            y = y[None]
            # The inputs before the first token that is not real: what the next chunk, or the first decode step, convolves with.
            tail = jax.lax.dynamic_slice_in_dim(window, n_real, K - 1, 1)
            new = {
                "conv": jax.lax.dynamic_update_slice_in_dim(cache["conv"], tail, slot, 0),
                "state": jax.lax.dynamic_update_slice_in_dim(cache["state"], state[None], slot, 0),
            }
    y = y + w["D"] * u
    out = (y * jax.nn.silu(z.astype(F32))).astype(x.dtype) @ w["out_proj"]
    return out, y.astype(x.dtype), new


# ---- its leaves and its cache ----


def mixer_shapes(d_model: int, d_inner: int, d_state: int, d_conv: int, dt_rank: int, dtype, out_fan_in: float) -> dict:
    """``name -> (shape, fan_in, dtype)`` of the mixer's leaves that are plain
    draws, as ``layer_list.draw`` reads them under a layer's ``ssm`` (``A_log``,
    ``dt_bias``, ``dt_proj`` and ``D`` are made by :func:`published_init`; the
    inner norms' scales by the family that has them). ``out_fan_in`` is
    ``out_proj``'s (a caller that scales its residual writers gives ``d_inner``
    times that)."""
    return {
        "in_proj": ((d_model, 2 * d_inner), d_model, dtype),
        "conv_w": ((d_conv, d_inner), d_conv, F32),
        "conv_b": ((d_inner,), d_conv, F32),
        "x_proj": ((d_inner, dt_rank + 2 * d_state), d_inner, dtype),
        "out_proj": ((d_inner, d_model), out_fan_in, dtype),
    }


def published_init(key, d_inner: int, d_state: int, dt_rank: int, dtype) -> dict:
    """The published Mamba initialisation of the four leaves no plain draw
    makes: ``A_log[n, c] = log(n + 1)``, ``dt_bias`` the inverse softplus of a
    step log-uniform in [1e-3, 1e-1], ``D`` ones, ``dt_proj`` uniform in ``+-
    dt_rank ^ -1/2``."""
    import math

    kd, kp = jax.random.split(key)
    dt = jnp.exp(jax.random.uniform(kd, (d_inner,), F32, math.log(1e-3), math.log(1e-1)))
    return dict(
        A_log=jnp.broadcast_to(jnp.log(jnp.arange(1, d_state + 1, dtype=F32))[:, None], (d_state, d_inner)),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        dt_proj=jax.random.uniform(kp, (dt_rank, d_inner), F32, -(dt_rank ** -0.5), dt_rank ** -0.5).astype(dtype),
        D=jnp.ones((d_inner,), F32),
    )


def init_state(slots: int, d_inner: int, d_state: int, d_conv: int, dtype) -> dict:
    """A Mamba-1 layer's cache: the last ``d_conv - 1`` inputs of its
    convolution and its scan's float32 state, channels minor."""
    return {
        "conv": jnp.zeros((slots, d_conv - 1, d_inner), dtype),
        "state": jnp.zeros((slots, d_state, d_inner), F32),
    }
