"""Vision Transformer (ViT) for image classification.

Reference analog: none in-tree (the reference's model zoo lives in user
containers — SURVEY.md §2); this extends the rebuild's model families
(ResNet, BERT, Llama) with the standard ViT architecture (patchify →
transformer encoder → classification head), which maps onto the TPU far
better than convnets: the whole network is large matmuls for the MXU,
with none of ResNet's batch-norm HBM reduce traffic.

TPU-first choices:
- patch embedding as one strided conv (= a single matmul per patch grid
  on the MXU), NHWC layout;
- bf16 compute / f32 params, LayerNorm statistics in f32;
- encoder blocks under ``lax.scan`` (one compiled block × depth) with
  the same logical-axis annotations the LM stack uses ("embed", "heads",
  "mlp"), so dp/fsdp/tp meshes shard it with the existing rule table;
- optional pallas flash attention (``attn_impl="flash"``) for large
  token counts; the 196-token ImageNet grid stays dense (S << the
  flash crossover).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from .common import remat_policy as _remat_policy

Dtype = Any


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_classes: int = 1000
    d_model: int = 768
    depth: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    attn_impl: str = "dense"  # "dense" | "flash"
    # Rematerialize each encoder block in backward (jax.checkpoint under
    # the layer scan, like LlamaConfig.remat): trades ~1/3 more FLOPs for
    # O(depth) activation memory -> larger batches fit (the round-2 ViT-B
    # bench was batch-capped at 64 by activation HBM; VERDICT r2 Weak #2).
    remat: bool = False
    # Remat policy when remat=True — same semantics as
    # LlamaConfig.remat_policy: "full" saves only block boundaries;
    # "dots" saves batch-dim-free GEMM outputs so backward skips
    # recomputing the MXU-bound work.
    remat_policy: str = "full"

    @property
    def grid(self) -> int:
        if self.image_size % self.patch_size:
            raise ValueError(
                f"image {self.image_size} not divisible by patch {self.patch_size}"
            )
        return self.image_size // self.patch_size

    @property
    def seq_len(self) -> int:
        return self.grid * self.grid + 1  # + [CLS]


def vit_s16(**over) -> ViTConfig:
    return ViTConfig(**{"d_model": 384, "depth": 12, "n_heads": 6, "d_ff": 1536, **over})


def vit_b16(**over) -> ViTConfig:
    return ViTConfig(**over)


def vit_l16(**over) -> ViTConfig:
    return ViTConfig(
        **{"d_model": 1024, "depth": 24, "n_heads": 16, "d_ff": 4096, **over}
    )


BY_NAME = {"s16": vit_s16, "b16": vit_b16, "l16": vit_l16}


class EncoderBlock(nn.Module):
    """Pre-norm transformer encoder block (bidirectional attention)."""

    cfg: ViTConfig

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        B, S, D = x.shape
        H = cfg.n_heads
        hd = D // H

        y = nn.LayerNorm(dtype=cfg.dtype, name="attn_norm")(x)
        qkv_init = nn.with_logical_partitioning(
            nn.initializers.xavier_uniform(), ("embed", "heads", "head_dim")
        )
        q = nn.DenseGeneral((H, hd), dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                            kernel_init=qkv_init, name="q_proj")(y)
        k = nn.DenseGeneral((H, hd), dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                            kernel_init=qkv_init, name="k_proj")(y)
        v = nn.DenseGeneral((H, hd), dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                            kernel_init=qkv_init, name="v_proj")(y)
        if cfg.attn_impl == "flash":
            from ..ops.flash_attention import flash_attention

            out = flash_attention(q, k, v, causal=False)
        else:
            s = jnp.einsum(
                "bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32
            ) / jnp.sqrt(hd).astype(jnp.float32)
            p = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
            out = jnp.einsum("bhst,bthd->bshd", p, v)
        out = nn.DenseGeneral(
            D, axis=(-2, -1), dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), ("heads", "head_dim", "embed")
            ),
            name="o_proj",
        )(out)
        x = x + out

        y = nn.LayerNorm(dtype=cfg.dtype, name="mlp_norm")(x)
        y = nn.Dense(
            cfg.d_ff, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), ("embed", "mlp")
            ),
            name="up_proj",
        )(y)
        y = nn.gelu(y)
        y = nn.Dense(
            D, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), ("mlp", "embed")
            ),
            name="down_proj",
        )(y)
        return x + y, None


class ViT(nn.Module):
    """images [B, H, W, 3] → logits [B, num_classes].

    Deliberately regularizer-free (no dropout knob): the benchmark/test
    configs never use one, and a config field no code reads would be a
    silent no-op trap.
    """

    cfg: ViTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B = x.shape[0]
        x = x.astype(cfg.dtype)
        # Patchify: one strided conv = a matmul over the patch grid.
        x = nn.Conv(
            cfg.d_model,
            (cfg.patch_size, cfg.patch_size),
            strides=(cfg.patch_size, cfg.patch_size),
            padding="VALID",
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), (None, None, None, "embed")
            ),
            name="patch_embed",
        )(x)
        x = x.reshape(B, -1, cfg.d_model)  # [B, grid², D]

        cls = self.param(
            "cls",
            nn.with_logical_partitioning(
                nn.initializers.zeros_init(), (None, None, "embed")
            ),
            (1, 1, cfg.d_model),
            cfg.param_dtype,
        )
        x = jnp.concatenate(
            [jnp.broadcast_to(cls.astype(cfg.dtype), (B, 1, cfg.d_model)), x],
            axis=1,
        )
        pos = self.param(
            "pos_embed",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (None, "seq", "embed")
            ),
            (1, cfg.seq_len, cfg.d_model),
            cfg.param_dtype,
        )
        x = x + pos.astype(cfg.dtype)

        block = EncoderBlock
        if cfg.remat:
            block = nn.remat(
                EncoderBlock, prevent_cse=False, policy=_remat_policy(cfg)
            )
        ScanBlocks = nn.scan(
            block,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            length=cfg.depth,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )
        x, _ = ScanBlocks(cfg, name="layers")(x, None)

        x = nn.LayerNorm(dtype=cfg.dtype, name="final_norm")(x)
        x = x[:, 0]  # [CLS]
        return nn.Dense(
            cfg.num_classes,
            dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.zeros_init(), ("embed", None)
            ),
            name="head",
        )(x)
