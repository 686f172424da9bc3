"""Bucketized hash-join probe with a VMEM-resident build table.

GPU TQP probes a global hash table with atomics-built chains; the TPU
adaptation is partition-then-probe: upstream radix partitioning (the shuffle
machinery) bounds each partition's build side so its bucket table fits VMEM,
then this kernel probes row blocks against the whole (B, C) bucket table held
resident in VMEM.

Layout: the build side is arranged (ops.py, sort-based, no atomics) into
  bkeys (B, C) int32 — C-way buckets, empty slots = sentinel
  bvals (B, C) int32 — payload row indices
Probe: bucket = murmur32(key) % B; compare the key against all C candidate
lanes at once (vectorized, fixed probe length — no data-dependent loops);
matched payload or -1.

The candidate fetch is a one-hot MXU matmul, not a gather (Mosaic has no
row gather from a VMEM table): every int32 plane enters the kernel as its
four unsigned bytes in bf16, transposed to (C, B), and ``onehot(bucket) @
bytes^T`` returns each probe row's C candidates byte by byte.  A byte is
exact in bf16 and exactly one product is non-zero, so the f32 accumulator
holds the byte exactly and the int32 plane reassembles bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import I32_ZERO
from repro.kernels.radix_hist.kernel import murmur32

SENTINEL = jnp.int32(-2147483648)
# (blk, B) one-hot tile bound, in elements: keeps the probe tile a few MiB
_ONEHOT_MAX = 1 << 20
_NEG1 = np.int32(-1)


def bucket_of(lo: jax.Array, hi: jax.Array, buckets: int) -> jax.Array:
    """Bucket id of a 64-bit key split into int32 (lo, hi) planes.

    Shared by the pure-JAX build (ops.build_bucket_table64) and the probe
    kernel below — both sides MUST hash identically.  The planes are combined
    through a second murmur round (hash_combine-style): a plain ``lo ^ hi``
    collapses packed two-column keys whose low word spans a small domain
    (e.g. partkey<<32 | suppkey) into few distinct inputs."""
    mixed = jax.lax.bitcast_convert_type(murmur32(hi), jnp.int32) ^ lo
    return (murmur32(mixed) % jnp.uint32(buckets)).astype(jnp.int32)


def table_bytes(*planes: jax.Array) -> jax.Array:
    """(B, C) int32 planes -> (4 * planes, C, B) bf16 unsigned bytes, the
    layout the kernels' one-hot fetch reads (plane-major, low byte first)."""
    out = []
    for p in planes:
        u = jax.lax.bitcast_convert_type(p, jnp.uint32).T          # (C, B)
        out += [((u >> (8 * j)) & 0xFF).astype(jnp.bfloat16)
                for j in range(4)]
    return jnp.stack(out)


def fetch_rows(bucket: jax.Array, tab_ref, planes: int) -> list[jax.Array]:
    """Rows ``bucket`` (blk, 1) of each int32 plane held as bytes in
    ``tab_ref`` (``table_bytes`` layout) -> ``planes`` arrays (blk, C)."""
    blk = bucket.shape[0]
    buckets = tab_ref.shape[2]
    iota = jax.lax.broadcasted_iota(jnp.int32, (blk, buckets), 1)
    onehot = (bucket == iota).astype(jnp.float32).astype(jnp.bfloat16)
    rows = []
    for p in range(planes):
        v = None
        for j in range(4):
            byte = jax.lax.dot_general(
                onehot, tab_ref[4 * p + j],
                dimension_numbers=(((1,), (1,)), ((), ())),    # onehot @ T^T
                preferred_element_type=jnp.float32).astype(jnp.int32)
            byte = byte if j == 0 else byte << np.int32(8 * j)
            v = byte if v is None else v | byte
        rows.append(v)
    return rows


def probe_block(n: int, buckets: int, blk: int) -> int:
    """Probe rows per grid step: ``blk`` capped so the (blk, B) one-hot tile
    stays within ``_ONEHOT_MAX`` elements, a multiple of 8 dividing ``n``."""
    blk = min(blk, max(8, _ONEHOT_MAX // buckets))
    while n % blk:
        blk //= 2
    assert blk % 8 == 0 or blk == n, (n, blk)
    return blk


def _kernel(pk_ref, tab_ref, out_ref, *, buckets: int):
    keys = pk_ref[...]                                    # (blk, 1)
    b = (murmur32(keys) % jnp.uint32(buckets)).astype(jnp.int32)
    cand_k, cand_v = fetch_rows(b, tab_ref, 2)            # (blk, C) each
    hit = cand_k == keys
    # unique build keys: at most one hit per row
    out_ref[...] = jnp.max(jnp.where(hit, cand_v, _NEG1), axis=1,
                           keepdims=True)


def _kernel64(plo_ref, phi_ref, tab_ref, out_ref, *, buckets: int):
    lo = plo_ref[...]                                     # (blk, 1)
    hi = phi_ref[...]
    b = bucket_of(lo, hi, buckets)
    cand_lo, cand_hi, cand_v = fetch_rows(b, tab_ref, 3)  # (blk, C) each
    hit = (cand_lo == lo) & (cand_hi == hi)
    out_ref[...] = jnp.max(jnp.where(hit, cand_v, _NEG1), axis=1,
                           keepdims=True)


def _probe_call(kernel, probe_planes, tab, blk, interpret):
    n = probe_planes[0].shape[0]
    grid = (n // blk,)
    row = pl.BlockSpec((blk, 1), lambda i: (i, I32_ZERO))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[row] * len(probe_planes) + [
            pl.BlockSpec(tab.shape,                         # resident
                         lambda i: (I32_ZERO, I32_ZERO, I32_ZERO))],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        interpret=interpret,
    )(*[p.reshape(n, 1).astype(jnp.int32) for p in probe_planes], tab)[:, 0]


def hash_probe_pallas(probe_keys: jax.Array, bkeys: jax.Array,
                      bvals: jax.Array, blk: int = 2048,
                      interpret: bool = False) -> jax.Array:
    """probe_keys (n,) int32; bucket table (B, C) -> matched row idx or -1."""
    n = probe_keys.shape[0]
    buckets = bkeys.shape[0]
    blk = probe_block(n, buckets, blk)
    return _probe_call(functools.partial(_kernel, buckets=buckets),
                       [probe_keys], table_bytes(bkeys, bvals), blk,
                       interpret)


def hash_probe64_pallas(probe_lo: jax.Array, probe_hi: jax.Array,
                        bk_lo: jax.Array, bk_hi: jax.Array,
                        bvals: jax.Array, blk: int = 2048,
                        interpret: bool = False) -> jax.Array:
    """64-bit-key probe: (n,) int32 lo/hi planes vs (B, C) plane pair.

    Same partition-then-probe scheme as ``hash_probe_pallas``; full 64-bit
    equality is checked in-kernel by comparing both planes, so int64 join keys
    (including two-column keys packed by ``combine_keys``) probe exactly."""
    n = probe_lo.shape[0]
    buckets = bk_lo.shape[0]
    blk = probe_block(n, buckets, blk)
    return _probe_call(functools.partial(_kernel64, buckets=buckets),
                       [probe_lo, probe_hi], table_bytes(bk_lo, bk_hi, bvals),
                       blk, interpret)
