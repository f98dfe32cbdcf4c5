"""What a family's reference and weights may share without knowing any
block's shape: the precision the references compute in, the stated lower
precisions (the controls), RMSNorm, the plain Adafactor that follows the
trainer's, and seeded draws of leaves by path. Moved here from the ``llama``
family's files (PR 26), so that a second family depends on no other family's
private names. It imports nothing of the program.
"""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

highest = functools.partial(jax.default_matmul_precision, "highest")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def identity(a):
    return a


# ---- stated precisions, and the next one down ----


def fake_int(w, axis, levels):
    """Symmetric per-channel integer rounding over ``axis`` (the axis the
    product contracts): scale = max|w| / levels, values in [-levels, levels].
    levels 127 is the int8 the serving configuration states; 7 is int4."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, jnp.finfo(jnp.float32).tiny) / levels
    return jnp.clip(jnp.round(w / scale), -levels, levels) * scale


def scaled_round(a, dtype, top):
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
    return (a / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def fp8_round(a):
    """A product's operand in per-tensor scaled float8, as fp8 training does
    it: e4m3 on the way forward, and the gradient that comes back through it
    in e5m2. The next precision below the bfloat16 the training
    configuration states."""
    return scaled_round(a, jnp.float8_e4m3fn, 448.0)


fp8_round.defvjp(
    lambda a: (fp8_round(a), None),
    lambda _, ct: (scaled_round(ct, jnp.float8_e5m2, 57344.0),),
)


# ---- the optimizer the training configurations state ----


def factored_axes(shape):
    """The two largest axes, as optax's Adafactor picks them (stable order;
    the larger is averaged away in the row statistic), or None."""
    if len(shape) < 2:
        return None
    order = sorted(range(len(shape)), key=lambda i: shape[i])
    if shape[order[-2]] < 128:
        return None
    return order[-2], order[-1]


def adafactor_scaled(g, v, step):
    """g over the factored estimate of its root mean square (Shazeer & Stern
    2018 as the training configuration states it: decay 1 - t^-0.8, eps
    1e-30, factored where two axes reach 128). Returns (u, new statistics)."""
    decay = 1.0 - (step + 1.0) ** -0.8
    g2 = g * g + 1e-30
    axes = factored_axes(g.shape)
    if axes is None:
        nv = decay * v["v"] + (1 - decay) * g2
        return g * nv ** -0.5, {"v": nv}
    d1, d0 = axes
    row = decay * v["row"] + (1 - decay) * jnp.mean(g2, axis=d0)
    col = decay * v["col"] + (1 - decay) * jnp.mean(g2, axis=d1)
    rd1 = d1 - 1 if d1 > d0 else d1
    rfac = (row / jnp.mean(row, axis=rd1, keepdims=True)) ** -0.5
    u = g * jnp.expand_dims(rfac, d0) * jnp.expand_dims(col ** -0.5, d1)
    return u, {"row": row, "col": col}


def zero_stats(shape):
    axes = factored_axes(shape)
    if axes is None:
        return {"v": jnp.zeros(shape, jnp.float32)}
    d1, d0 = axes
    drop = lambda ax: tuple(s for i, s in enumerate(shape) if i != ax)
    return {"row": jnp.zeros(drop(d0), jnp.float32), "col": jnp.zeros(drop(d1), jnp.float32)}


def bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


# ---- seeded leaves ----


def leaf_key(key, path):
    return jax.random.fold_in(key, zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF)


def draw(key, shape, fan_in, dtype):
    if fan_in is None:
        return jnp.ones(shape, dtype)
    w = jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)
    return w.astype(dtype)


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return out
