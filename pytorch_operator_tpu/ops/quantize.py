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

- Quantized leaves stay **int8 in HBM**, and a product reads them there.
  The serving forward (``models.llama.decode_forward``) hands each layer's
  :class:`QuantizedTensor` leaves to the modules that multiply by them,
  and those call :meth:`QuantizedTensor.project`: the int8 payload is
  converted (exactly) inside the product's own fusion, the product
  accumulates in float32, and the per-channel scale multiplies the small
  result. The other order, ``dequantize()`` then product, this module used
  to claim "always fuses into the product's operand read". It did for the
  2-D kernels and in the decode loop; in the serving engine's prefill
  chunk (128 rows a product) the compiler wrote a bfloat16 copy of every
  layer's 3-D ``q_proj`` / ``k_proj`` / ``v_proj`` kernel to HBM at every
  call, 403 MB out for 201 MB in, and read that instead (PERF.md section
  6, PR 31: found in the compiled program and timed in the chip's trace).
  With the scale behind the product no dequantised weight exists as a
  value that could be written anywhere. What the compiler may still copy
  is the int8 payload itself, where a layer's kernel has to be sliced out
  of a scan-stacked parameter first: the serving stack therefore holds
  its layers a tree each (``models.llama.per_layer_params``), and a
  kernel is then an argument of the program as it lies.
  ``dequantize_tree`` remains for the consumers that want plain arrays
  (``Llama.__call__``'s scan under ``map_variables``, the expert banks,
  tests).
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

    def project(self, x: jax.Array) -> jax.Array:
        """``x [..., in] @ w [in, *out]`` for a weight quantized over its
        leading (contracted) axis, the scale BEHIND the product:
        ``(x @ q) * scale``, in ``x``'s dtype. One scale per output channel
        is constant along the contraction, so this is the same mathematics
        as ``x @ dequantize()``; the int8 -> ``x.dtype`` convert is exact
        (256 levels fit a bf16 mantissa), the product accumulates in
        float32 and the result is rounded once, where
        ``x @ dequantize(bf16)`` rounds the weight and then the product.
        The product's only large operand is the int8 payload as it lies
        in HBM (module docstring: what the compiler made of the other
        order)."""
        if self.scale.shape[0] != 1 or self.scale.shape[1:] != self.q.shape[1:]:
            raise ValueError(
                f"project needs one scale per output channel over the leading "
                f"axis; q {self.q.shape}, scale {self.scale.shape}"
            )
        y = jax.lax.dot_general(
            x, self.q.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (y * self.scale[0]).astype(x.dtype)


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
        if isinstance(node, list):  # layers held one tree each: the names are the layer's own
            return [walk(v, path) for v in node]
        axis = rule(path, node)
        return node if axis is None else quantize(node, axis)

    return walk(params, ())


def dequantize_tree(tree, dtype=jnp.float32):
    """Map :class:`QuantizedTensor` leaves back to arrays (identity on
    plain trees). Call this INSIDE the jitted consumer, so that the
    compiler may fuse the dequantisation into the product's operand read;
    where it must not write a copy, use :meth:`QuantizedTensor.project`
    (module docstring)."""
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
