"""Flash attention as a pallas TPU kernel (forward + custom-VJP backward).

Why a kernel at all: XLA fuses elementwise chains into matmuls well, but a
dense causal attention still materializes the [S, S] score matrix in HBM
(O(S^2) bytes) and round-trips it for softmax + PV. The flash form streams
K/V blocks through VMEM with an online softmax, so HBM traffic is O(S·D)
and the MXU stays fed from on-chip memory — the canonical memory-bound →
compute-bound rewrite for TPU (pallas_guide.md: HBM → VMEM → MXU).

Design notes:

- Grid ``(B·H, S/block_q, S/block_k)``; the K-block dimension is innermost
  and sequential, carrying the online-softmax state (running max ``m``,
  denominator ``l``, accumulator ``acc``) in VMEM scratch across grid
  steps. Fully-masked K blocks (above the causal diagonal) are skipped
  with ``pl.when`` — ~2x fewer FLOPs for causal LM.
- GQA without materialization: K/V block specs index with ``head // G``
  (G = query heads per KV head), so grouped heads read the same KV shard
  straight from HBM — no ``repeat`` before the kernel.
- Backward is the standard two-kernel flash recomputation (no [S, S]
  residual): forward saves only ``lse = m + log l`` per row; ``dq`` re-walks
  K blocks, ``dk/dv`` re-walks Q blocks, each recomputing ``p = exp(s -
  lse)`` on the fly. dK/dV are produced per *query* head and group-summed
  outside the kernel (keeps every grid cell's output block private).
- Matmuls run in the input dtype (bf16 in production) with
  ``preferred_element_type=float32``; softmax math is float32.
- Unaligned shapes (S not divisible by the blocks; D not lane-aligned)
  are zero-padded to the tiling and masked via a static ``kv_len``
  (padded key columns score -inf; padded query rows are sliced off), so
  e.g. S=197/D=64 runs the O(S·D) kernel instead of falling back
  to a dense O(S^2) path (round 4).
- Multi-device: pass ``mesh`` — the call is wrapped in a partial-manual
  ``shard_map`` over the dp/fsdp (batch) and tp (heads) axes, composing
  with the pjit-sharded training step the same way parallel/ring.py does
  for sp. Sequence parallelism is ring attention's job, not this kernel's.

Reference analog: none (SURVEY.md §2 — attention kernels live outside the
reference, in the user containers' PyTorch).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

_NEG = -1e30  # finite mask value: exp(_NEG - m) underflows to exactly 0.0


class _FlashCfg(NamedTuple):
    """Static kernel config (hashable — custom_vjp nondiff arg)."""

    causal: bool
    block_q: int
    block_k: int
    groups: int  # query heads per kv head (GQA)
    interpret: bool
    # Softmax scale — 1/sqrt(d) of the TRUE head dim: when the wrapper
    # zero-pads D to lane alignment, sqrt(padded D) would be wrong.
    scale: float
    # Keys/values at positions >= kv_len are masked out (score = -inf).
    # None = no length mask (every position is real). Static: this is the
    # one TRUE sequence length of a padded-to-alignment batch, not a
    # per-example length.
    kv_len: Optional[int] = None


def _mask_scores(cfg: _FlashCfg, s, i, j, bq: int, bk: int):
    """Element-level score masking shared by all three kernels (forward
    and backward MUST mask identically): causal upper triangle and/or
    key columns >= kv_len score _NEG."""
    import jax
    import jax.numpy as jnp

    if not cfg.causal and cfg.kv_len is None:
        return s
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * bq
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
    keep = cols <= rows if cfg.causal else True
    if cfg.kv_len is not None:
        keep = keep & (cols < cfg.kv_len)
    return jnp.where(keep, s, _NEG)


def _live_block(cfg: _FlashCfg, i, j, bq: int, bk: int):
    """Predicate for K blocks with at least one unmasked column under the
    causal and/or kv_len masks (None = every block live). ``i``/``j`` are
    the q/k block program ids of the calling grid."""
    live = None
    if cfg.causal:
        live = j * bk <= i * bq + bq - 1
    if cfg.kv_len is not None:
        past = j * bk < cfg.kv_len
        live = past if live is None else live & past
    return live


# ---------------------------------------------------------------- kernels


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, cfg: _FlashCfg):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i, j = pl.program_id(1), pl.program_id(2)
    bq, bk = cfg.block_q, cfg.block_k
    scale = cfg.scale

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def compute():
        q = q_ref[0]                       # [bq, D] input dtype
        k = k_ref[0]                       # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                          # [bq, bk] f32
        s = _mask_scores(cfg, s, i, j, bq, bk)
        m_prev = m_ref[:, :1]              # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)    # [bq, 1]
        p = jnp.exp(s - m_new)             # [bq, bk] f32; masked cols → 0
        l_ref[:] = jnp.broadcast_to(
            l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape,
        )
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                  # [bq, D] f32
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    live = _live_block(cfg, i, j, bq, bk)
    if live is None:
        compute()
    else:
        # Skip K blocks with no unmasked column: above the causal
        # diagonal, or entirely past kv_len.
        pl.when(live)(compute)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # lse carries a broadcast 128-lane dim purely for TPU tiling
        # (same layout as the in-tree pallas flash kernel's l/m outputs).
        lse_ref[0] = jnp.broadcast_to(m_ref[:, :1] + jnp.log(l), lse_ref.shape[1:])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, cfg: _FlashCfg):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i, j = pl.program_id(1), pl.program_id(2)
    bq, bk = cfg.block_q, cfg.block_k
    scale = cfg.scale

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = _mask_scores(cfg, s, i, j, bq, bk)
        p = jnp.exp(s - lse_ref[0, :, :1])          # [bq, bk] f32
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, :, :1])         # [bq, bk] f32
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    live = _live_block(cfg, i, j, bq, bk)
    if live is None:
        compute()
    else:
        pl.when(live)(compute)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, cfg: _FlashCfg):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    j, i = pl.program_id(1), pl.program_id(2)  # K block outer, Q block inner
    bq, bk = cfg.block_q, cfg.block_k
    scale = cfg.scale

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = _mask_scores(cfg, s, i, j, bq, bk)
        p = jnp.exp(s - lse_ref[0, :, :1])          # [bq, bk] f32
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # p^T @ do → [bk, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, :, :1])
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # ds^T @ q → [bk, D]

    live = _live_block(cfg, i, j, bq, bk)
    if live is None:
        compute()
    else:
        # Causal: this K block only sees Q blocks at or below the
        # diagonal. kv_len: K blocks past the true length are all-masked.
        pl.when(live)(compute)

    @pl.when(i == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------- pallas calls


def _specs(cfg: _FlashCfg, D: int, *, kv_from_j: bool):
    """Input specs for (q, k, v, do?, lse?, delta?) given the grid layout.

    ``kv_from_j=True``: grid is (bh, q_block i, k_block j) — fwd and dq.
    ``kv_from_j=False``: grid is (bh, k_block j, q_block i) — dkv.
    """
    from jax.experimental import pallas as pl

    G = cfg.groups

    if kv_from_j:
        q_idx = lambda b, i, j: (b, i, 0)       # noqa: E731
        kv_idx = lambda b, i, j: (b // G, j, 0)  # noqa: E731
    else:
        q_idx = lambda b, j, i: (b, i, 0)       # noqa: E731
        kv_idx = lambda b, j, i: (b // G, j, 0)  # noqa: E731

    q_spec = pl.BlockSpec((1, cfg.block_q, D), q_idx)
    kv_spec = pl.BlockSpec((1, cfg.block_k, D), kv_idx)
    # lse/delta are [BH, S, 128] (value broadcast over the 128-lane dim —
    # TPU tiling needs the last two block dims (block_q, 128)).
    row_spec = pl.BlockSpec((1, cfg.block_q, 128), q_idx)
    return q_spec, kv_spec, row_spec


def _flash_fwd_call(q, k, v, cfg: _FlashCfg):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    grid = (BH, S // cfg.block_q, S // cfg.block_k)
    q_spec, kv_spec, row_spec = _specs(cfg, D, kv_from_j=True)

    return pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            pl.BlockSpec((1, cfg.block_q, D), lambda b, i, j: (b, i, 0)),
            row_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((cfg.block_q, D), jnp.float32),
            pltpu.VMEM((cfg.block_q, 128), jnp.float32),
            pltpu.VMEM((cfg.block_q, 128), jnp.float32),
        ],
        interpret=cfg.interpret,
        name="flash_fwd",
    )(q, k, v)


def _flash_bwd_call(q, k, v, o, lse, do, cfg: _FlashCfg):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    # delta_i = rowsum(dO_i · O_i) — cheap, XLA fuses it. Broadcast over the
    # 128-lane dim to match the lse tiling layout.
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[..., None],
        (BH, S, 128),
    )

    q_spec, kv_spec, row_spec = _specs(cfg, D, kv_from_j=True)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, cfg=cfg),
        grid=(BH, S // cfg.block_q, S // cfg.block_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, cfg.block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((cfg.block_q, D), jnp.float32)],
        interpret=cfg.interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    q_spec, kv_spec, row_spec = _specs(cfg, D, kv_from_j=False)
    dkx, dvx = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg=cfg),
        grid=(BH, S // cfg.block_k, S // cfg.block_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[
            pl.BlockSpec((1, cfg.block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, cfg.block_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((cfg.block_k, D), jnp.float32),
            pltpu.VMEM((cfg.block_k, D), jnp.float32),
        ],
        interpret=cfg.interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)

    # Per-query-head dK/dV → per-KV-head (sum the G group members).
    G = cfg.groups
    if G > 1:
        BKV = BH // G
        dkx = dkx.reshape(BKV, G, S, D).sum(axis=1).astype(k.dtype)
        dvx = dvx.reshape(BKV, G, S, D).sum(axis=1).astype(v.dtype)
    return dq, dkx, dvx


# ---------------------------------------------------------- custom VJP


def _flash_fwd(q, k, v, cfg: _FlashCfg):
    o, lse = _flash_fwd_call(q, k, v, cfg)
    # The kernel emits lse as [BH, S, 128] (value broadcast over the lane
    # dim — TPU tiling); storing that as the fwd→bwd residual would cost
    # 128x the bytes of the [BH, S] values it holds (134 MB/layer at 8B
    # shapes). Save the slim column and re-broadcast in backward.
    return o, (q, k, v, o, lse[:, :, 0])


def _flash_bwd(cfg: _FlashCfg, res, do):
    import jax.numpy as jnp

    q, k, v, o, lse_slim = res
    lse = jnp.broadcast_to(lse_slim[..., None], lse_slim.shape + (128,))
    return _flash_bwd_call(q, k, v, o, lse, do, cfg)


_FLASH = None


def _flash(q, k, v, cfg: _FlashCfg):
    """The differentiable core on [B·H, S, D] layout (lazily built so this
    module imports without jax)."""
    global _FLASH
    if _FLASH is None:
        import jax

        @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
        def f(q, k, v, cfg):
            return _flash_fwd(q, k, v, cfg)[0]

        f.defvjp(_flash_fwd, _flash_bwd)
        _FLASH = f
    return _FLASH(q, k, v, cfg)


# ------------------------------------------------------------- public API


def _plan_tiling(S: int, D: int, block_q: int, block_k: int, interpret: bool):
    """Resolve block sizes and padded dims for a (possibly unaligned)
    shape: returns ``(block_q, block_k, S_pad, D_pad)`` with
    ``S_pad % block_q == S_pad % block_k == 0`` and, on real TPU
    (``interpret=False``), Mosaic's tiling minima honored: q-blocks
    sublane-aligned (%8), k-blocks and D lane-aligned (%128). Pure
    arithmetic — unit-testable for the TPU branch on any backend."""
    min_bq, min_bk = (8, 128) if not interpret else (1, 1)
    D_pad = -(-D // 128) * 128 if not interpret else D
    align = max(min_bq, min_bk)
    S_min = -(-S // align) * align  # smallest aligned padded length
    block_q = -(-min(block_q, S_min) // min_bq) * min_bq
    block_k = -(-min(block_k, S_min) // min_bk) * min_bk
    lcm = block_q * block_k // math.gcd(block_q, block_k)
    if lcm > max(block_q, block_k):
        # Unequal blocks where neither divides the other would pad S up
        # to their lcm — potentially several silent extra blocks of
        # work. Collapse both to the smaller size (lane-aligned, which
        # also satisfies the sublane minimum): at most one padded block.
        lcm = block_q = block_k = max(
            min(block_q, block_k) // min_bk * min_bk, min_bk
        )
    return block_q, block_k, -(-S // lcm) * lcm, D_pad


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    block_q: int = 1024,
    block_k: int = 1024,
    kv_len: Optional[int] = None,
    mesh=None,
    interpret: Optional[bool] = None,
):
    """Blockwise (flash) attention. q ``[B,S,H,D]``; k, v ``[B,S,KH,D]``
    with ``H % KH == 0`` (GQA). Returns ``[B,S,H,D]`` in q's dtype.

    Assumes rotary/positional encoding is already applied and token order
    is the standard causal layout (positions = arange).

    Shapes that don't fit the kernel's tiling (S not divisible by the
    block sizes; on real TPU also D % 128 != 0) are zero-PADDED to
    alignment and masked: padded key columns score -inf via the kernel's
    ``kv_len`` mask, padded query rows are sliced off the output, and the
    softmax scale stays 1/sqrt(true D) — numerics equal the dense oracle
    (round 4; previously these shapes fell back to the dense O(S^2)
    path, which materializes [B,H,S,S] f32 scores). Cost honesty:
    S-padding is bounded by one extra block row/column, but D-padding
    MULTIPLIES the attention FLOPs and q/k/v/o bytes by D_pad/D (2x for
    D=64) — a win at long S where the kernel's O(S·D) HBM beats the
    dense path's O(S^2), NOT for short-S/thin-D models: an earlier round
    measured a ViT-B shape (S=197, D=64) slower under the padded
    kernel than dense XLA.

    ``kv_len``: static TRUE sequence length when the caller's batch is
    already padded to S — keys/values at positions >= kv_len are masked
    out. One length for the whole batch (per-example lengths would need
    an array operand; compose ragged batches with segment packing
    instead).

    Default block sizes: an earlier round's end-to-end sweep in the 0.3b
    train step settled on 1024/1024. Under libtpu 0.0.34 on a v5e all
    three kernels compile at 1024/1024 with D=128 as they stand, with no
    ``compiler_params`` (chip run, PR 21; chip_smoke.py keeps checking
    them against ``_dense_reference`` at H=32, KH=8).

    ``mesh``: wrap in a partial-manual shard_map over the batch (dp, fsdp)
    and head (tp) mesh axes so the kernel composes with pjit sharding.
    ``interpret``: force pallas interpret mode; default = auto: on when
    the default backend is the CPU, where the tests run, off on TPU. A
    ``tpu_chips`` job cannot reach the interpreter by losing its chip:
    the supervisor pins its platform (runtime/env.py), so with no chip
    the replica fails at backend creation, before any model is built.
    Nothing on the training path gives way to ``_dense_reference`` either;
    that function is the tests' oracle only.
    """
    import jax

    B, S, H, D = q.shape
    KH = k.shape[2]
    assert H % KH == 0, f"H={H} not a multiple of KH={KH}"
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if kv_len is not None and not 0 < kv_len <= S:
        raise ValueError(f"kv_len={kv_len} outside (0, S={S}]")

    block_q, block_k, S_pad, D_pad = _plan_tiling(
        S, D, block_q, block_k, interpret
    )
    if S_pad != S and kv_len is None:
        kv_len = S  # padded key columns must not attend
    cfg = _FlashCfg(
        causal, block_q, block_k, H // KH, interpret,
        1.0 / math.sqrt(D), kv_len,
    )

    def core(q, k, v):
        b, s, h, d = q.shape
        kh = k.shape[2]
        pad = [(0, 0), (0, S_pad - s), (0, 0), (0, D_pad - d)]
        if S_pad != s or D_pad != d:
            q, k, v = (jax.numpy.pad(x, pad) for x in (q, k, v))
        q3 = q.transpose(0, 2, 1, 3).reshape(b * h, S_pad, D_pad)
        k3 = k.transpose(0, 2, 1, 3).reshape(b * kh, S_pad, D_pad)
        v3 = v.transpose(0, 2, 1, 3).reshape(b * kh, S_pad, D_pad)
        o3 = _flash(q3, k3, v3, cfg)
        o = o3.reshape(b, h, S_pad, D_pad).transpose(0, 2, 1, 3)
        return o[:, :s, :, :d]

    def live(axes):
        return [a for a in axes if mesh is not None and a in mesh.axis_names and mesh.shape[a] > 1]

    # Take manual control only of axes that evenly divide the operand dims
    # (e.g. flax's init traces with batch=1 — leave dp/fsdp to the compiler
    # there; it replicates, which is correct for tracing).
    batch_axes = live(("dp", "fsdp"))
    if batch_axes and B % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = []
    tp_axes = live(("tp",))
    if tp_axes and KH % mesh.shape["tp"]:
        tp_axes = []
    manual = batch_axes + tp_axes
    if not manual:
        return core(q, k, v)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    batch = tuple(batch_axes) or None
    if isinstance(batch, tuple) and len(batch) == 1:
        batch = batch[0]
    tp = "tp" if tp_axes else None
    q_spec = P(batch, None, tp, None)
    return shard_map(
        core,
        mesh=mesh,
        in_specs=(q_spec, q_spec, q_spec),
        out_specs=q_spec,
        axis_names=set(manual),
        # pallas_call out_shapes carry no varying-mesh-axes metadata, so
        # jax 0.9's VMA check cannot see through the kernel — disable it
        # for this wrapper (shardings are fully specified above).
        check_vma=False,
    )(q, k, v)


def _dense_reference(q, k, v, *, causal: bool):
    """Dense XLA attention: the numerics oracle for tests and for
    chip_smoke.py. No code path falls back to it."""
    import jax
    import jax.numpy as jnp

    B, S, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    qg = q.reshape(B, S, KH, G, D)
    s = jnp.einsum(
        "bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32
    ) / math.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkgst,btkd->bskgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, S, H, D).astype(q.dtype)
