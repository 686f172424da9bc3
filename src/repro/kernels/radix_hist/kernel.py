"""Radix-partition histogram kernel — shuffle capacity planning / skew stats
/ counting-rank dispatch.

For each row block, bin the (int32) key and produce a per-block partition
histogram (nblocks, P).  The per-block resolution is what the adaptive
capacity planner, the skew monitor, AND the shuffle dispatch rank consume
(paper §3.5: shuffle time = max over nodes of send/recv bytes — per-block
histograms expose that before any data moves; an exclusive prefix sum over
the same histograms ranks every row within its partition without a sort).

Two binning modes:
  * ``hashed=True``  — bin = murmur32(key) % parts (capacity planning over
    raw join keys; splitmix64 needs 64-bit multiplies the VPU lacks, so the
    in-kernel hash is the murmur3 32-bit finalizer, see DESIGN.md).
  * ``hashed=False`` — bin = key % parts (keys are already destination ids,
    e.g. the shuffle dispatch path where splitmix64 ran outside the kernel).

Histogram accumulation is a one-hot + MXU matmul, like segsum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import I32_ZERO


def murmur32(k: jax.Array) -> jax.Array:
    """murmur3 fmix32 — vector-friendly 32-bit finalizer."""
    k = k.astype(jnp.uint32)
    k = k ^ (k >> 16)
    k = k * jnp.uint32(0x85EBCA6B)
    k = k ^ (k >> 13)
    k = k * jnp.uint32(0xC2B2AE35)
    k = k ^ (k >> 16)
    return k


def _bin(k: jax.Array, parts: int, hashed: bool) -> jax.Array:
    if hashed:
        return (murmur32(k) % jnp.uint32(parts)).astype(jnp.int32)
    return (k.astype(jnp.uint32) % jnp.uint32(parts)).astype(jnp.int32)


def _kernel(key_ref, out_ref, *, blk: int, parts: int, width: int,
            hashed: bool):
    pid = _bin(key_ref[...], parts, hashed)               # (blk, 1) i32
    iota = jax.lax.broadcasted_iota(jnp.int32, (blk, width), 1)
    onehot = (pid == iota).astype(jnp.float32)
    ones = jnp.ones((blk, 1), jnp.float32)
    hist = jax.lax.dot_general(onehot, ones,
                               dimension_numbers=(((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (W, 1)
    out_ref[...] = hist.T                                  # (1, W)


def radix_hist_pallas(keys: jax.Array, parts: int, width: int | None = None,
                      blk: int = 2048, interpret: bool = False,
                      hashed: bool = True) -> jax.Array:
    """keys (n,) int32 -> per-block histograms (n//blk, width) float32.

    ``parts`` is the bin modulo; ``width`` (>= parts, default 128-padded) is
    the lane-aligned output width — columns beyond parts stay zero."""
    n = keys.shape[0]
    width = width or max(128, (parts + 127) // 128 * 128)
    assert n % blk == 0 and width >= parts
    grid = (n // blk,)
    return pl.pallas_call(
        functools.partial(_kernel, blk=blk, parts=parts, width=width,
                          hashed=hashed),
        grid=grid,
        in_specs=[pl.BlockSpec((blk, 1), lambda i: (i, I32_ZERO))],
        out_specs=pl.BlockSpec((pl.squeezed, 1, width),
                               lambda i: (i, I32_ZERO, I32_ZERO)),
        out_shape=jax.ShapeDtypeStruct((n // blk, 1, width), jnp.float32),
        interpret=interpret,
    )(keys.reshape(n, 1).astype(jnp.int32))[:, 0]


# ---------------------------------------------------------------------------
# fused counting rank: histogram + intra-block exclusive rank in ONE kernel
# ---------------------------------------------------------------------------

def _rank_kernel(key_ref, slot_ref, hist_ref, run_ref, *, blk: int,
                 width: int, parts: int):
    """One grid step = one row block, executed SEQUENTIALLY (TPU grid order):

      1. one-hot the block's bins (hashed=False binning: keys are ids);
      2. exclusive intra-block rank per key via a strictly-lower-triangular
         ones matmul on the MXU (row i's rank = earlier same-key rows);
      3. add the running per-key total carried in VMEM scratch across blocks
         (the prefix sum the jnp oracle computes as a separate pass);
      4. extract each row's own rank through the one-hot (lane reduce).

    All counts stay <= blk per block so the f32 matmul is exact; the running
    total is carried in int32, so ranks are exact for any n < 2^31 — exactly
    the oracle's contract.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        run_ref[...] = jnp.zeros_like(run_ref)

    pid = _bin(key_ref[...], parts, False)                     # (blk, 1)
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (blk, width), 1)
    onehot = (pid == iota_w).astype(jnp.float32)               # (blk, W)
    rows = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
    lower = (cols < rows).astype(jnp.float32)                  # strict lower
    excl = jax.lax.dot_general(lower, onehot,
                               dimension_numbers=(((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    rank = run_ref[0:1, :] + excl.astype(jnp.int32)            # (blk, W)
    sel = jnp.where(pid == iota_w, rank, I32_ZERO)
    slot_ref[...] = jnp.sum(sel, axis=1, keepdims=True,
                            dtype=jnp.int32)                   # (blk, 1)
    bh = jnp.sum(onehot, axis=0, keepdims=True)                # (1, W)
    hist_ref[...] = bh
    run_ref[0:1, :] = run_ref[0:1, :] + bh.astype(jnp.int32)


def counting_rank_pallas(keys: jax.Array, parts: int, width: int,
                         blk: int = 512, interpret: bool = False,
                         ) -> tuple[jax.Array, jax.Array]:
    """keys (n,) int32 ids in [0, parts) -> (slot (n,) int32, hist (n//blk,
    width) f32): the whole shuffle-dispatch rank on-chip in one pass.

    ``blk`` bounds the (blk, blk) triangular tile (512 -> 1 MB VMEM); the
    rank produced is independent of the block size.
    """
    n = keys.shape[0]
    assert n % blk == 0 and width >= parts
    grid = (n // blk,)
    slot, hist = pl.pallas_call(
        functools.partial(_rank_kernel, blk=blk, width=width, parts=parts),
        grid=grid,
        in_specs=[pl.BlockSpec((blk, 1), lambda i: (i, I32_ZERO))],
        out_specs=[pl.BlockSpec((blk, 1), lambda i: (i, I32_ZERO)),
                   pl.BlockSpec((pl.squeezed, 1, width),
                                lambda i: (i, I32_ZERO, I32_ZERO))],
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.int32),
                   jax.ShapeDtypeStruct((n // blk, 1, width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((8, width), jnp.int32)],
        interpret=interpret,
    )(keys.reshape(n, 1).astype(jnp.int32))
    return slot[:, 0], hist[:, 0]
