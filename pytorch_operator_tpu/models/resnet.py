"""ResNet v1.5 for image classification — the headline benchmark model.

Reference analog: the ResNet-50 ImageNet PyTorchJob config (BASELINE.json:8,
"DDP → xla backend on v5p-8"); the model itself lives in the reference's
user containers (torchvision), so this is a from-scratch flax implementation
of the standard v1.5 architecture (stride-2 in the 3×3 of each bottleneck —
the MLPerf convention).

TPU-first choices:
- NHWC layout (XLA's native conv layout on TPU),
- bfloat16 compute / float32 params and batch-norm statistics (MXU-friendly
  without accuracy loss; ``bn_f32_stats=False`` is an experimental knob that
  drops BN stats AND BN scale/bias to bf16),
- no data-dependent control flow — the whole step is one XLA program.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class BottleneckBlock(nn.Module):
    """1×1 → 3×3(stride) → 1×1(×4) with projection shortcut when needed."""

    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        # Zero-init the last BN scale: residual branches start as identity,
        # the standard trick for stable large-batch training.
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), self.strides, name="conv_proj"
            )(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class BasicBlock(nn.Module):
    """3×3 → 3×3 residual block (ResNet-18/34)."""

    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters, (1, 1), self.strides, name="conv_proj"
            )(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class SpaceToDepthStem(nn.Module):
    """The 7×7/stride-2 stem conv, computed as a 4×4/stride-1 conv on a
    2×2 space-to-depth transform of the input — mathematically EXACT.

    Why: with 3 input channels the MXU runs the 7×7 conv mostly on padding
    (channel dim is packed far below the systolic array's native width).
    Space-to-depth moves 2×2 spatial blocks into channels (3→12), which
    packs the contraction 4× denser at identical FLOPs — the standard
    MLPerf-era TPU ResNet stem optimization.

    Exactness: zero-pad the 7×7 kernel to 8×8 (one extra top row / left
    column), then for output (i,j):
        y[i,j] = Σ_{u,v∈0..7} K8[u,v] · x[2i+u−4, 2j+v−4]
    splitting u=2r+a, v=2s+b (r,s∈0..3; a,b∈0..1) turns the sum into a
    4×4 stride-1 conv over z[p,q,(a,b,c)] = x[2p+a, 2q+b, c] with spatial
    padding (2,1) — same outputs, same gradients (the kernel reshape is
    linear). The parameter keeps the canonical (7,7,C,F) shape, so
    checkpoints interop with the plain stem.
    """

    features: int
    dtype: Any
    kernel_init: Callable

    @nn.compact
    def __call__(self, x):
        n, h, w, c = x.shape
        if h % 2 or w % 2:
            raise ValueError(f"space-to-depth stem needs even H/W, got {(h, w)}")
        k7 = self.param(
            "kernel", self.kernel_init, (7, 7, c, self.features), jnp.float32
        )
        k8 = jnp.pad(k7, ((1, 0), (1, 0), (0, 0), (0, 0)))
        # K8[2r+a, 2s+b, c, o] → K4[r, s, (a,b,c), o]; (a,b,c) flattens in
        # the same order as the z channel layout below.
        k4 = (
            k8.reshape(4, 2, 4, 2, c, self.features)
            .transpose(0, 2, 1, 3, 4, 5)
            .reshape(4, 4, 4 * c, self.features)
        )
        z = (
            x.reshape(n, h // 2, 2, w // 2, 2, c)
            .transpose(0, 1, 3, 2, 4, 5)
            .reshape(n, h // 2, w // 2, 4 * c)
        )
        return jax.lax.conv_general_dilated(
            z.astype(self.dtype),
            k4.astype(self.dtype),
            window_strides=(1, 1),
            padding=((2, 1), (2, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    block_cls: ModuleDef = BottleneckBlock
    # Batch-norm precision. f32 (default) is the numerically safe choice
    # for convergence runs. False computes the BN reductions in bf16 AND
    # (a flax constraint: stats are stored in param_dtype) downcasts the
    # learnable scale/bias to bf16 — so their SGD updates quantize to an
    # 8-bit mantissa too. An earlier round measured it throughput-
    # neutral; kept as an experiment knob only.
    bn_f32_stats: bool = True
    # Compute the stem as a space-to-depth 4×4 conv (exact; see
    # SpaceToDepthStem). Same parameters/checkpoints either way.
    s2d_stem: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(
            nn.Conv,
            use_bias=False,
            dtype=self.dtype,
            kernel_init=nn.initializers.variance_scaling(2.0, "fan_out", "normal"),
        )
        norm = partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,
            # flax computes stats in max(param_dtype, f32) unless
            # force_float32_reductions; bf16 stats need both relaxed.
            param_dtype=jnp.float32 if self.bn_f32_stats else self.dtype,
            force_float32_reductions=self.bn_f32_stats,
        )
        act = nn.relu

        x = x.astype(self.dtype)
        if self.s2d_stem:
            x = SpaceToDepthStem(
                features=self.num_filters,
                dtype=self.dtype,
                kernel_init=nn.initializers.variance_scaling(
                    2.0, "fan_out", "normal"
                ),
                name="conv_init",
            )(x)
        else:
            x = conv(self.num_filters, (7, 7), (2, 2), padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = act(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(
                    filters=self.num_filters * 2**i,
                    strides=strides,
                    conv=conv,
                    norm=norm,
                    act=act,
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(
            self.num_classes,
            dtype=jnp.float32,
            kernel_init=nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal"),
        )(x)
        return x


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=BasicBlock)
ResNet34 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3])
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3])
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3])

BY_DEPTH = {18: ResNet18, 34: ResNet34, 50: ResNet50, 101: ResNet101, 152: ResNet152}
