"""Weight-only int8 quantization for the inference (decode) path.

Reference analog: none — the reference is a training operator and any
quantization lives in its user containers. The rebuild motivation is
the decode step's own arithmetic: at 0.3b scale it is bound by a
per-step issue floor (bf16 weights barely beat f32), but the step
becomes weight-STREAMING bound as the model grows —
and at 8B the bf16 weights alone (16 GB) exceed a v5e chip's HBM, so
the flagship config cannot decode on one chip at all without shrinking
the bytes. Symmetric per-channel int8 cuts the streamed weight bytes
4x vs f32 (2x vs bf16) at ~0.4% RMS weight error.

TPU-first mechanics, and why this is NOT a "dequantize then run" wrapper:

- Quantized leaves stay **int8 in HBM**. ``dequantize_tree`` is traced
  *inside* the jitted decode step, so the emitted HLO is
  ``convert(s8) * scale`` feeding each matmul — XLA fuses that
  elementwise chain into the dot's operand read (the same fusion this
  tree already leans on for its f32-param → bf16-compute casts
  everywhere), so no full-size bf16/f32 copy of the weights ever
  materializes; the per-step HBM traffic is the int8 bytes.
- Inside ``lax.scan`` decode loops the dequant is loop-invariant, but
  XLA's while-loop code motion declines to hoist size-inflating ops
  (a convert s8→f32 quadruples bytes), so the fusion — and the memory
  win — survives the scan. Verified empirically by 8B decoding on one
  chip at all (a hoisted dequant would OOM instantly).
- Scales are per-OUTPUT-channel over each weight's contraction axis
  (the axis the matmul reduces), the standard accuracy/shape trade:
  one f32 per output column, broadcast along the reduction.

Scope: inference only. Training keeps full-precision master weights
(``--param-dtype`` covers the bf16-params recipe); int8 *activation*
quantization (for MXU int8 matmul throughput) is a different trade and
deliberately out of scope — decode is bandwidth-bound, not FLOP-bound,
so weight-only captures the win without touching numerics of the
activations.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantizedTensor:
    """An int8-quantized weight: ``w ≈ q.astype(f32) * scale``.

    ``q`` keeps the original weight's shape; ``scale`` is f32 with the
    same rank, extent 1 along the quantization (contraction) axis —
    broadcastable, so ``dequantize`` is one fused convert+multiply.
    """

    q: jax.Array
    scale: jax.Array

    @property
    def shape(self):
        return self.q.shape

    def dequantize(self, dtype=jnp.float32) -> jax.Array:
        return (self.q.astype(jnp.float32) * self.scale).astype(dtype)


def quantize(w: jax.Array, axis: int) -> QuantizedTensor:
    """Symmetric per-channel int8: scale = max|w| / 127 over ``axis``."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, jnp.finfo(jnp.float32).tiny) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127)
    return QuantizedTensor(q=q.astype(jnp.int8), scale=scale)


def contract_axis(path: tuple, leaf: Any) -> int | None:
    """Which axis a matmul reduces for this param leaf, or None to keep
    the leaf unquantized.

    Name-based on the llama/bert param vocabulary, with NEGATIVE axes so
    scan-stacked leaves (leading ``layers`` axis) and unstacked leaves
    share one rule:

    - ``q/k/v_proj kernel`` ``[..., embed, heads, head_dim]`` → -3
    - any other ``kernel``  ``[..., in, out]``                → -2
      (o_proj, gate/up/down_proj, lm_head)
    - ``embedding``         ``[..., vocab, embed]`` → -1 (per-row: the
      lookup "reduces" nothing, but decode streams the whole table for
      the head-tied case and rows are the natural channel)
    - MoE expert banks ``w_in``/``w_out`` ``[..., E, in, out]`` → -2
    - everything else (norm ``scale``s, MoE router ``gate``, biases):
      None — tiny, and the router's argmax is precision-sensitive.
    """
    name = str(path[-1]) if path else ""
    parent = str(path[-2]) if len(path) > 1 else ""
    if name == "embedding":
        axis = -1
    elif name == "kernel":
        axis = -3 if parent in ("q_proj", "k_proj", "v_proj") else -2
    elif name in ("w_in", "w_out"):
        axis = -2
    else:
        return None
    if getattr(leaf, "ndim", 0) < -axis:
        return None
    return axis


def quantize_tree(params, *, rule=contract_axis):
    """Quantize a (plain, unboxed) params tree's matmul weights to
    :class:`QuantizedTensor` leaves; non-weight leaves pass through.
    Jit-friendly (``jax.jit(quantize_tree)`` quantizes on-device).
    """

    def walk(node, path):
        if isinstance(node, Mapping):
            return type(node)(
                {k: walk(v, path + (k,)) for k, v in node.items()}
            )
        axis = rule(path, node)
        return node if axis is None else quantize(node, axis)

    return walk(params, ())


def dequantize_tree(tree, dtype=jnp.float32):
    """Map :class:`QuantizedTensor` leaves back to arrays (identity on
    plain trees). Call this INSIDE the jitted consumer — see module
    docstring — so the dequant fuses into the matmul operand reads
    instead of materializing a full-precision weight copy."""
    return jax.tree.map(
        lambda leaf: (
            leaf.dequantize(dtype) if isinstance(leaf, QuantizedTensor) else leaf
        ),
        tree,
        is_leaf=lambda leaf: isinstance(leaf, QuantizedTensor),
    )


def tree_bytes(tree) -> int:
    """Total payload bytes (QuantizedTensor counts q + scale)."""
    total = 0
    for leaf in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    ):
        arrs = (leaf.q, leaf.scale) if isinstance(leaf, QuantizedTensor) else (leaf,)
        total += sum(a.size * a.dtype.itemsize for a in arrs)
    return total
