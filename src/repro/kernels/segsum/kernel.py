"""One-hot MXU grouped aggregation (TQP's aggregation-as-matmul, TPU-native).

Grouped sum of (n, C) values into (G, C) buckets as a blocked
one-hot(gid) @ values matmul: each (BLK, G) one-hot tile and (BLK, C) value
tile live in VMEM and feed the MXU; the (G, C) accumulator stays resident in
VMEM across the row-block grid (output index_map pins every step to block 0).

This replaces the CUDA hash-table+atomics aggregation of GPU TQP: the TPU has
no fast global atomics, but a 128x128 systolic matmul turns scatter-reduce
into dense compute at ~100% MXU utilization when G is modest (dict-encoded
group domains — exactly TPC-H's shape).

``segment_minmax_pallas`` is the masked-reduce sibling for min/max: the same
(BLK, G) one-hot tile selects values (identity elsewhere) and a VPU lane
reduction folds each block into the (1, G) accumulator — grouped min/max with
no sort and no atomics, completing the sortless aggregation operator set.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import I32_ZERO


def _kernel(gid_ref, val_ref, out_ref, *, blk: int, gblk: int):
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    gid = gid_ref[...] - pl.program_id(0) * gblk        # (blk, 1) int32
    iota = jax.lax.broadcasted_iota(jnp.int32, (blk, gblk), 1)
    onehot = (gid == iota).astype(val_ref.dtype)         # (blk, gblk)
    # HIGHEST: at the MXU's default precision float32 values enter as
    # bfloat16, and a group's sum drifts by ~1e-3 relative on v5e
    out_ref[...] += jax.lax.dot_general(
        onehot, val_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),      # onehot^T @ vals
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=out_ref.dtype)


def segment_sum_pallas(gids: jax.Array, values: jax.Array, groups: int,
                       blk: int = 1024, gblk: int | None = None,
                       interpret: bool = False) -> jax.Array:
    """gids (n,) int32 in [0, groups); values (n, C) float -> (G, C) sums.

    Callers pad n to a multiple of blk and route padding rows to a dead group
    (ops.py handles both).  G and C should be multiples of 128 for MXU
    alignment.  The grid runs over blocks of ``gblk`` groups (default: all
    G), each sweeping every row block, so the VMEM working set is
    blk*(gblk + C)*4 + gblk*C*4 bytes whatever G is.  Accumulation dtype
    follows ``values.dtype`` (float32 on hardware; float64 is available
    under interpret mode, where the MXU is emulated by jnp).
    """
    n, c = values.shape
    gblk = gblk or groups
    assert n % blk == 0 and groups % gblk == 0, (n, blk, groups, gblk)
    grid = (groups // gblk, n // blk)
    return pl.pallas_call(
        functools.partial(_kernel, blk=blk, gblk=gblk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk, 1), lambda j, i: (i, I32_ZERO)),
            pl.BlockSpec((blk, c), lambda j, i: (i, I32_ZERO)),
        ],
        out_specs=pl.BlockSpec((gblk, c), lambda j, i: (j, I32_ZERO)),
        out_shape=jax.ShapeDtypeStruct((groups, c), values.dtype),
        interpret=interpret,
    )(gids.reshape(n, 1).astype(jnp.int32), values)


def _minmax_kernel(gid_ref, val_ref, out_ref, *, blk: int, gblk: int,
                   is_min: bool):
    step = pl.program_id(1)
    ident = jnp.asarray(jnp.inf if is_min else -jnp.inf, out_ref.dtype)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref[...], ident)

    gid = gid_ref[...] - pl.program_id(0) * gblk        # (blk, 1) int32
    iota = jax.lax.broadcasted_iota(jnp.int32, (blk, gblk), 1)
    # one-hot select: group's own rows keep their value, everything else the
    # reduction identity — a (blk, gblk) tile folded by a VPU lane reduction
    masked = jnp.where(gid == iota, val_ref[...], ident)  # (blk, gblk)
    red = (jnp.min if is_min else jnp.max)(masked, axis=0, keepdims=True)
    out_ref[...] = (jnp.minimum if is_min else jnp.maximum)(out_ref[...], red)


def segment_minmax_pallas(gids: jax.Array, values: jax.Array, groups: int,
                          is_min: bool, blk: int = 1024,
                          gblk: int | None = None,
                          interpret: bool = False) -> jax.Array:
    """gids (n,) int32 in [0, groups); values (n,) float -> (G,) min/max.

    Empty groups hold the reduction identity (+/-inf); callers drop them (the
    relational layer masks empty slots before compaction).  Groups are
    blocked by ``gblk`` as in :func:`segment_sum_pallas`.
    """
    n = values.shape[0]
    gblk = gblk or groups
    assert n % blk == 0 and groups % gblk == 0, (n, blk, groups, gblk)
    grid = (groups // gblk, n // blk)
    out = pl.pallas_call(
        functools.partial(_minmax_kernel, blk=blk, gblk=gblk,
                          is_min=is_min),
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk, 1), lambda j, i: (i, I32_ZERO)),
            pl.BlockSpec((blk, 1), lambda j, i: (i, I32_ZERO)),
        ],
        out_specs=pl.BlockSpec((1, gblk), lambda j, i: (I32_ZERO, j)),
        out_shape=jax.ShapeDtypeStruct((1, groups), values.dtype),
        interpret=interpret,
    )(gids.reshape(n, 1).astype(jnp.int32), values.reshape(n, 1))
    return out[0]
