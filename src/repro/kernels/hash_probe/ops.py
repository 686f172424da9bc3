"""Public wrapper: sort-based bucket-table build + blocked probe.

Build is pure JAX (stable argsort by bucket — no atomics); overflowing
buckets (> capacity) raise the recorded overflow flag so callers re-bucket
with a bigger table, mirroring the exchange layer's capacity discipline.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret
from repro.kernels.radix_hist.kernel import murmur32
from .kernel import SENTINEL, bucket_of, hash_probe_pallas, hash_probe64_pallas
from .ref import hash_probe_ref


def _next_pow2(x: int) -> int:
    return 1 << max(3, (x - 1).bit_length())


def next_pow2(x: int) -> int:
    """Public alias (relational-layer bucket sizing)."""
    return _next_pow2(x)


def _split64(keys: jax.Array) -> tuple[jax.Array, jax.Array]:
    """int64 keys -> (lo, hi) int32 planes (bit-exact)."""
    k = keys.astype(jnp.int64)
    lo = jax.lax.bitcast_convert_type(
        (k & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32), jnp.int32)
    hi = (k >> 32).astype(jnp.int32)
    return lo, hi


@partial(jax.jit, static_argnames=("buckets", "cap"))
def build_bucket_table(keys: jax.Array, vals: jax.Array, buckets: int,
                       cap: int = 8):
    """(m,) unique int32 keys -> ((B, C) keys, (B, C) vals, overflowed)."""
    m = keys.shape[0]
    b = (murmur32(keys.astype(jnp.int32)) % jnp.uint32(buckets)).astype(jnp.int32)
    order = jnp.argsort(b, stable=True)
    sb = b[order]
    counts = jax.ops.segment_sum(jnp.ones((m,), jnp.int32), b,
                                 num_segments=buckets)
    start = jnp.concatenate([jnp.zeros(1, jnp.int32),
                             jnp.cumsum(counts, dtype=jnp.int32)[:-1]])
    slot = jnp.arange(m, dtype=jnp.int32) - start[sb]
    flat = sb * cap + jnp.minimum(slot, cap - 1)
    keep = slot < cap
    flat = jnp.where(keep, flat, buckets * cap)
    bk = jnp.full((buckets * cap,), SENTINEL, jnp.int32).at[flat].set(
        keys.astype(jnp.int32)[order], mode="drop").reshape(buckets, cap)
    bv = jnp.full((buckets * cap,), -1, jnp.int32).at[flat].set(
        vals.astype(jnp.int32)[order], mode="drop").reshape(buckets, cap)
    return bk, bv, jnp.any(counts > cap)


@partial(jax.jit, static_argnames=("buckets", "cap"))
def build_bucket_table64(keys: jax.Array, vals: jax.Array, buckets: int,
                         cap: int = 16, valid: jax.Array | None = None):
    """(m,) unique int64 keys -> ((B,C) lo, (B,C) hi, (B,C) vals, overflowed).

    Two int32 key planes hold the full 64-bit key so packed two-column join
    keys probe exactly.  ``valid`` masks out padding rows (they are routed to
    a virtual bucket and dropped — deferred-compaction tables index without
    compacting first).  One stable argsort by bucket — no atomics.
    """
    m = keys.shape[0]
    k64 = keys.astype(jnp.int64)
    lo, hi = _split64(k64)
    b = bucket_of(lo, hi, buckets)
    if valid is not None:
        b = jnp.where(valid, b, buckets)          # virtual bucket: dropped
    iota = jnp.arange(m, dtype=jnp.int32)
    # one sort by (bucket, key): bucket ranking AND adjacent exact duplicates.
    # Duplicate keys are kept once — membership probes (semi/anti) then accept
    # non-unique build sides without inflating any bucket; ties pick the
    # smallest key's first row, which is irrelevant under the unique-build
    # contract of join_unique.
    sb, sk, order = jax.lax.sort((b, k64, iota), num_keys=2, is_stable=True)
    in_bucket = sb < buckets
    dup = jnp.concatenate([jnp.zeros((1,), bool),
                           (sb[1:] == sb[:-1]) & (sk[1:] == sk[:-1])])
    keep = in_bucket & ~dup
    counts = jax.ops.segment_sum(keep.astype(jnp.int32), sb,
                                 num_segments=buckets + 1,
                                 indices_are_sorted=True)[:buckets]
    start = jnp.concatenate([jnp.zeros(1, jnp.int32),
                             jnp.cumsum(counts, dtype=jnp.int32)])
    rank = jnp.cumsum(keep.astype(jnp.int32)) - 1  # global rank among kept
    slot = rank - start[jnp.minimum(sb, buckets)]
    flat = sb * cap + jnp.minimum(slot, cap - 1)
    ok = keep & (slot < cap)
    flat = jnp.where(ok, flat, buckets * cap)     # OOB -> dropped
    slo = lo[order]
    shi = hi[order]
    bk_lo = jnp.full((buckets * cap,), SENTINEL, jnp.int32).at[flat].set(
        slo, mode="drop").reshape(buckets, cap)
    bk_hi = jnp.full((buckets * cap,), SENTINEL, jnp.int32).at[flat].set(
        shi, mode="drop").reshape(buckets, cap)
    bv = jnp.full((buckets * cap,), -1, jnp.int32).at[flat].set(
        vals.astype(jnp.int32)[order], mode="drop").reshape(buckets, cap)
    return bk_lo, bk_hi, bv, jnp.any(counts > cap)


_PAD64 = (1 << 62) + 1  # never a real key nor KEY_SENTINEL; pads probe blocks


def hash_probe64(probe_keys: jax.Array, bk_lo: jax.Array, bk_hi: jax.Array,
                 bvals: jax.Array, blk: int = 2048,
                 interpret: bool | None = None) -> jax.Array:
    """(n,) int64 probe keys vs a 64-bit bucket table -> build row idx or -1."""
    if interpret is None:
        interpret = auto_interpret()
    n = probe_keys.shape[0]
    blk = min(blk, max(8, (n + 7) // 8 * 8))
    npad = (n + blk - 1) // blk * blk
    pk = jnp.full((npad,), _PAD64, jnp.int64).at[:n].set(
        probe_keys.astype(jnp.int64))
    lo, hi = _split64(pk)
    out = hash_probe64_pallas(lo, hi, bk_lo, bk_hi, bvals, blk=blk,
                              interpret=interpret)
    return out[:n]


@partial(jax.jit, static_argnames=("blk", "cap", "interpret", "use_kernel"))
def hash_join_probe(probe_keys: jax.Array, build_keys: jax.Array,
                    build_vals: jax.Array, blk: int = 2048, cap: int = 8,
                    interpret: bool | None = None, use_kernel: bool = True):
    """End-to-end probe: returns (matched row idx or -1, build overflowed).

    VMEM budget: the (B, C) tables must fit resident — B*C*16 bytes as
    bf16 bytes; with the default C=8 and B = 2*next_pow2(m)/C this is ~32
    bytes per build row.  ``interpret=None`` auto-selects: compiled on TPU,
    interpret elsewhere.
    """
    if interpret is None:
        interpret = auto_interpret()
    if not use_kernel:
        return hash_probe_ref(probe_keys, build_keys, build_vals), jnp.asarray(False)
    m = build_keys.shape[0]
    buckets = max(128, _next_pow2(2 * max(1, m)) // cap)
    bk, bv, ov = build_bucket_table(build_keys, build_vals, buckets, cap)
    n = probe_keys.shape[0]
    blk = min(blk, max(8, (n + 7) // 8 * 8))
    npad = (n + blk - 1) // blk * blk
    pk = jnp.full((npad,), SENTINEL, jnp.int32).at[:n].set(
        probe_keys.astype(jnp.int32))
    out = hash_probe_pallas(pk, bk, bv, blk=blk, interpret=interpret)
    return out[:n], ov


def hash_join_probe_auto(probe_keys, build_keys, build_vals, cap: int = 8,
                         max_tries: int = 4, **kw):
    """Host-level capacity escalation: double bucket capacity on overflow.

    Standalone-kernel convenience only.  The relational engine does NOT use
    this local retry loop: ``relational.build_index`` surfaces the overflow
    flag, the backends fold it into ``ctx.overflow``, and the fault runner's
    capacity-factor escalation (which also scales the per-bucket capacity via
    ``_BaseContext.bucket_cap``) re-executes the whole query — the same
    re-execution discipline as shuffle overflow (paper §2.4)."""
    for _ in range(max_tries):
        out, ov = hash_join_probe(probe_keys, build_keys, build_vals,
                                  cap=cap, **kw)
        if not bool(ov):
            return out, cap
        cap *= 2
    raise RuntimeError(f"bucket overflow persists at cap={cap}")
