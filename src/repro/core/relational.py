"""Static-shape relational operators in pure JAX (the per-device TQP compute layer).

TPU adaptation (DESIGN.md §2): no atomics / no dynamic shapes, so
  * filter        = O(n) validity-mask merge (deferred compaction — no sort)
  * join          = one of three :class:`BuildIndex` methods, each built
                    once per plan per build side (unique build keys — every
                    TPC-H join is FK->PK once plans order probe/build sides):
                    ``direct`` (a slot per value of a provably dense key
                    domain, one gather per probe row), ``sorted`` (sorted
                    build, ``searchsorted`` probe) or ``hash`` (the Pallas
                    bucket-table probe, ``kernels/hash_probe``)
  * group-by      = sortless when the key domain is provably small (dense
                    group ids + the ``kernels/segsum`` one-hot MXU reduce —
                    aggregation-as-matmul); otherwise ONE stable argsort over
                    a packed int64 key + segment reductions reusing that
                    order for every aggregate
  * order-by      = ONE multi-operand stable ``lax.sort`` with validity
                    sentinels (single HLO sort regardless of key count)

Deferred-compaction invariant
-----------------------------
Operators accept both compact (``valid is None``) and masked tables and
preserve ``count == valid_mask().sum()``.  Mask-producing ops (``filter_rows``,
``join_unique``, ``semi_join``, ``anti_join``) are sort-free; the O(cap log cap)
front-compaction runs only where contiguity is genuinely required:
``sort_by`` (output is ordered hence compact), ``limit`` / ``static_shrink``
(slicing), and exchange payload packing (``exchange.broadcast_table``).

Sort-count budget per operator (HLO ``sort`` ops; enforced by
``benchmarks/bench_sort_tax.py`` and the CI regression gate):

  filter_rows                    0
  join_unique / left_join /      0 probe-side; per *distinct* build index
    semi / anti                  0 on the direct path (planner-proven dense
                                 key domain, ``DIRECT_JOIN_SPAN``), 1 on the
                                 sorted path
  group_aggregate                0 with provable ``key_bits`` (packed domain
                                 <= 2^13: direct addressing via the segsum
                                 one-hot kernel), 0 with a claimed
                                 ``groups_hint`` (trace-time hash-compaction
                                 dictionary, ``kernels/hash_group``) or no
                                 key columns (scalar aggregation); 1 otherwise
  sort_by                        1 (any number of keys)
  shuffle (exchange)             0 (radix-hist counting rank), output masked
  compact / ensure_compact       1, boundaries only

``key_bits`` is no longer hand-threaded by query code: ``core/planner.py``
derives it (and ``groups_hint``, and each join's build-key ``key_range``) by
bound propagation over the logical plan (``core/plan.py``) and passes it
here — the physical contract of this module is unchanged, only the *source*
of the widths moved from comments at call sites into a compiler pass.  Every
such claim is re-checked at run time: a valid row outside it raises the
overflow flag, never a silent miss.

The joins, the group-by, compaction and ordering trace inside their
``rel.*`` operator scopes (``core/tracing.py``), so a profile charges each
device operation to the operator that emitted it.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import tracing
from .table import Table, KEY_SENTINEL
# imported at module scope (not lazily inside traced code): the kernel modules
# materialize constants at import time, which must not happen under a trace
from repro.kernels.hash_group import ops as _hg_ops
from repro.kernels.hash_probe import ops as _hp_ops
from repro.kernels.segsum import ops as _ss_ops

# Largest packed-key domain (2^bits) the direct-addressing aggregation will
# take on: one-hot tiles are (blk, 2^bits) in VMEM, so 13 bits (8192 slots,
# 64 lane-tiles) is the practical MXU ceiling; larger domains fall back to
# the single-sort path.
DIRECT_AGG_BITS_MAX = 13
# Most slots per build row a direct-address join index may spend: the index
# holds one int32 slot per value of the proven key domain [lo, hi].  TPC-H's
# primary keys are dense; at sf 1 the ratio is 4 for o_orderkey (6 000 000
# values over 1 500 000 orders) and 1 for c_custkey, p_partkey, s_suppkey and
# n_nationkey.  A sparser domain falls back to the sorted index.
DIRECT_JOIN_SPAN = 8
# Most slots of a direct-address join index whatever the build's size: 2^26
# int32 slots are 256 MB, 1.6 % of a v5e chip's 16 GB of HBM.
DIRECT_JOIN_SLOTS_MAX = 1 << 26
# Largest table an ORDER BY ranks pairwise instead of sorting, on TPU only:
# XLA:TPU took 475 s to compile a stable (float64, int64) sort of 33,280
# rows for v5e, while ranking 2^18 rows pairwise is ~7*10^10 compares.
PAIRWISE_ORDER_MAX = 1 << 18
# Largest claimed group bound the hash-compaction path will take on: the
# dictionary is sized groups_hint * capacity_factor (<= 8192 slots at the
# default factor), keeping both the dictionary planes and the segsum one-hot
# tiles inside the same VMEM ceiling as the direct path.
HASH_AGG_GROUPS_MAX = 4096
# Which engine backs the sortless reductions (segsum / radix_hist):
#   REPRO_AGG_KERNEL=auto (default) — Pallas kernels on TPU, jnp
#     scatter-reduce everywhere else.  Interpret-mode Pallas is a correctness
#     vehicle, not a fast path: its grid loop re-slices full buffers per step,
#     a 20-90x wall-clock tax on CPU — while the jnp path lowers to the same
#     sort-free HLO, so the sort-tax win is identical.
#   REPRO_AGG_KERNEL=1 — force the kernels (the CI leg that exercises them
#     through all 22 query plans, in interpret mode off-TPU).
#   REPRO_AGG_KERNEL=0 — force the jnp oracle (the CI leg that pins the
#     kernels' reference semantics).
# Resolved lazily on first use: probing jax.default_backend() at import time
# would finalize the JAX backend as an import side effect, breaking drivers
# that call jax.distributed.initialize() after importing repro.
_AGG_KERNEL_CACHE: bool | None = None


def agg_kernel_default() -> bool:
    global _AGG_KERNEL_CACHE
    if _AGG_KERNEL_CACHE is None:
        env = os.environ.get("REPRO_AGG_KERNEL", "auto").lower()
        if env in ("1", "true", "kernel"):
            _AGG_KERNEL_CACHE = True
        elif env in ("0", "false", "oracle"):
            _AGG_KERNEL_CACHE = False
        else:
            _AGG_KERNEL_CACHE = jax.default_backend() == "tpu"
    return _AGG_KERNEL_CACHE

__all__ = [
    "compact",
    "ensure_compact",
    "filter_rows",
    "combine_keys",
    "BuildIndex",
    "build_index",
    "direct_join_fits",
    "probe_index",
    "join_unique",
    "semi_join",
    "anti_join",
    "left_join",
    "group_aggregate",
    "sort_by",
    "sort_limit",
    "limit",
    "static_shrink",
    "hash_partition_ids",
]

_I64 = jnp.int64
_HASH_C1 = np.uint64(0xFF51AFD7ED558CCD)
_HASH_C2 = np.uint64(0xC4CEB9FE1A85EC53)


# ---------------------------------------------------------------------------
# compaction / filtering
# ---------------------------------------------------------------------------

@tracing.op_scope(tracing.COMPACT)
def compact(t: Table, keep: jax.Array) -> Table:
    """Move rows where ``keep & valid`` to the front; count = how many.

    This is the expensive boundary operator (one stable argsort over the full
    capacity) — hot paths defer it via masked tables (see module docstring).
    """
    keep = keep & t.valid_mask()
    order = jnp.argsort(~keep, stable=True)  # keep=True rows first, stable
    cols = {k: v[order] for k, v in t.columns.items()}
    return Table(cols, keep.sum().astype(jnp.int32))


def ensure_compact(t: Table) -> Table:
    """Materialize the front-compaction of a masked table (no-op if compact)."""
    if t.valid is None:
        return t
    return compact(t, t.valid)


def filter_rows(t: Table, mask: jax.Array) -> Table:
    """O(n) filter: merge ``mask`` into the validity mask — no sort."""
    keep = mask & t.valid_mask()
    return Table(dict(t.columns), keep.sum().astype(jnp.int32), keep)


def limit(t: Table, n: int) -> Table:
    """First n valid rows (callers sort first).  Statically shrinks capacity."""
    t = ensure_compact(t)
    cols = {k: v[:n] for k, v in t.columns.items()}
    return Table(cols, jnp.minimum(t.count, n).astype(jnp.int32))


def static_shrink(t: Table, new_capacity: int) -> tuple[Table, jax.Array]:
    """Shrink capacity (planner's selectivity hint).  Returns (table, overflowed).

    Overflow (count > new_capacity) signals the fault-tolerant runner to retry
    with a larger capacity — the static-shape analogue of the paper's
    size-metadata exchange guarding receive-buffer allocation.
    """
    t = ensure_compact(t)
    overflow = t.count > new_capacity
    cols = {k: v[:new_capacity] for k, v in t.columns.items()}
    return Table(cols, jnp.minimum(t.count, new_capacity).astype(jnp.int32)), overflow


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------

def combine_keys(cols: Sequence[jax.Array], bits: Sequence[int] | None = None,
                 ) -> jax.Array:
    """Pack non-negative int key columns into one int64 sort/group/join key.

    Without ``bits``: exactly the seed behavior — at most two columns
    (< 2^31 each) packed with 32-bit shifts; more must be packed explicitly by
    the plan so collision-freedom is provable.

    With ``bits``: any number of columns, ``bits[i]`` the provable width of
    column i (``0 <= cols[i] < 2^bits[i]``), ``sum(bits) <= 63`` — the plan
    states its widths and gets a single collision-free key for one-sort
    multi-column ORDER BY / GROUP BY.
    """
    if bits is not None:
        if len(bits) != len(cols):
            raise ValueError("combine_keys: len(bits) != len(cols)")
        if sum(bits) > 63:
            raise ValueError(f"combine_keys: {sum(bits)} key bits > 63")
        k = jnp.zeros_like(cols[0], dtype=_I64)
        for c, b in zip(cols, bits):
            k = (k << b) | c.astype(_I64)
        return k
    if len(cols) > 2:
        raise ValueError("pack >2 keys explicitly in the plan (collision safety)")
    k = cols[0].astype(_I64)
    for c in cols[1:]:
        k = (k << 32) | c.astype(_I64)
    return k


def _valid_key(t: Table, key: jax.Array) -> jax.Array:
    """Key column with invalid rows forced to the +inf sentinel."""
    return jnp.where(t.valid_mask(), key.astype(_I64), KEY_SENTINEL)


def hash_partition_ids(key: jax.Array, num_partitions: int) -> jax.Array:
    """Fingerprint-based destination ids for shuffle (splitmix64 finalizer)."""
    k = key.astype(_I64).astype(jnp.uint64)
    k = (k ^ (k >> 33)) * _HASH_C1
    k = (k ^ (k >> 33)) * _HASH_C2
    k = k ^ (k >> 33)
    return (k % np.uint64(num_partitions)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# joins (unique build side)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BuildIndex:
    """Reusable probe structure over a unique-key build side.

    Built once per (build table, key) pair and cached per plan by the backend
    contexts, so a dimension table probed by several joins is indexed once.
    Three methods:

      * ``direct``: one int32 slot per value of a key domain ``[lo, hi]``
        the planner proved, holding the build row with that key or -1; a
        probe is one gather.  Built by one scatter, no sort.  A valid build
        key outside the domain raises ``overflow``.
      * ``sorted``: keys sorted once, probes are ``searchsorted`` (pure JAX —
        the always-available fallback).
      * ``hash``: (B, C) bucket table of 32-bit key planes probed by the
        Pallas kernel in ``repro.kernels.hash_probe`` — fixed probe length,
        no log-factor, bucket table VMEM-resident on TPU.
    """

    method: str
    capacity: int
    overflow: jax.Array
    # direct: slots[key - lo] = build row, or -1
    lo: int | None = None
    slots: jax.Array | None = None
    # sorted
    sorted_keys: jax.Array | None = None
    sorted_rows: jax.Array | None = None
    # hash (two int32 planes hold the full 64-bit key)
    bk_lo: jax.Array | None = None
    bk_hi: jax.Array | None = None
    bvals: jax.Array | None = None


def direct_join_fits(key_range: tuple[int, int] | None,
                     capacity: int) -> bool:
    """Whether a build of ``capacity`` rows whose keys lie in ``key_range``
    takes the direct-address index: at most ``DIRECT_JOIN_SPAN`` slots per
    build row and ``DIRECT_JOIN_SLOTS_MAX`` slots in all."""
    if key_range is None:
        return False
    span = key_range[1] - key_range[0] + 1
    return 1 <= span <= min(DIRECT_JOIN_SPAN * max(1, capacity),
                            DIRECT_JOIN_SLOTS_MAX)


@tracing.op_scope(tracing.JOIN_BUILD)
def build_index(build: Table, build_key: jax.Array, method: str = "sorted",
                bucket_cap: int = 16,
                key_range: tuple[int, int] | None = None) -> BuildIndex:
    """Index the build side of a unique-key join.

    ``method="direct"`` needs ``key_range``, the proven ``(lo, hi)`` of the
    valid build keys, and falls back to ``sorted`` where
    :func:`direct_join_fits` refuses the domain."""
    bkey = _valid_key(build, build_key)
    if method == "direct":
        if key_range is None:
            raise ValueError("direct join index needs a key_range")
        if direct_join_fits(key_range, build.capacity):
            return _build_direct(bkey, build.capacity, *key_range)
        method = "sorted"
    if method == "sorted":
        order = jnp.argsort(bkey)
        return BuildIndex("sorted", build.capacity, jnp.asarray(False),
                          sorted_keys=bkey[order], sorted_rows=order)
    if method != "hash":
        raise ValueError(f"unknown join method {method!r}")
    rows = jnp.arange(build.capacity, dtype=jnp.int32)
    buckets = max(128, _hp_ops.next_pow2(2 * max(1, build.capacity)) // 4)
    bk_lo, bk_hi, bv, ov = _hp_ops.build_bucket_table64(
        bkey, rows, buckets, cap=bucket_cap, valid=bkey != KEY_SENTINEL)
    return BuildIndex("hash", build.capacity, ov,
                      bk_lo=bk_lo, bk_hi=bk_hi, bvals=bv)


def _build_direct(bkey: jax.Array, cap: int, lo: int, hi: int) -> BuildIndex:
    """Direct-address index over the key domain ``[lo, hi]``: one scatter of
    each valid build row's index to its key's slot.  Where keys repeat (a
    semi-join's build) the slot keeps the lowest row, the row the sorted
    index finds.  Invalid rows (sentinel keys) are dropped; a valid key
    outside the domain raises the overflow flag, so a stale or lying range
    reruns conservatively instead of missing matches."""
    span = hi - lo + 1
    valid = bkey != KEY_SENTINEL
    in_range = (bkey >= lo) & (bkey <= hi)
    off = jnp.where(valid & in_range, bkey - lo, span).astype(jnp.int32)
    rows = jnp.arange(cap, dtype=jnp.int32)
    slots = jnp.full((span,), cap, jnp.int32).at[off].min(rows, mode="drop")
    return BuildIndex("direct", cap, jnp.any(valid & ~in_range), lo=lo,
                      slots=jnp.where(slots == cap, -1, slots))


@tracing.op_scope(tracing.JOIN_PROBE)
def probe_index(index: BuildIndex, probe_key: jax.Array,
                probe_valid: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Probe an index.  Returns (matched, build_row_idx); idx arbitrary where
    unmatched (callers mask through ``matched``)."""
    pk = probe_key.astype(_I64)
    if index.method == "direct":
        hi = index.lo + index.slots.shape[0] - 1
        in_range = (pk >= index.lo) & (pk <= hi) & probe_valid & \
            (pk != KEY_SENTINEL)
        row = index.slots[jnp.where(in_range, pk - index.lo, 0)
                          .astype(jnp.int32)]
        return in_range & (row >= 0), jnp.maximum(row, 0)
    if index.method == "sorted":
        pos = jnp.searchsorted(index.sorted_keys, pk)
        pos = jnp.minimum(pos, index.capacity - 1)
        matched = (index.sorted_keys[pos] == pk) & probe_valid & \
            (pk != KEY_SENTINEL)
        return matched, index.sorted_rows[pos]
    row = _hp_ops.hash_probe64(pk, index.bk_lo, index.bk_hi, index.bvals)
    matched = (row >= 0) & probe_valid & (pk != KEY_SENTINEL)
    return matched, jnp.maximum(row, 0)


def _probe(probe_key: jax.Array, probe_valid: jax.Array,
           build: Table, build_key: jax.Array, index: BuildIndex | None,
           method: str):
    if index is None:
        index = build_index(build, build_key, method)
    return probe_index(index, probe_key, probe_valid)


def join_unique(probe: Table, build: Table, probe_on: jax.Array,
                build_on: jax.Array, take: Sequence[str],
                index: BuildIndex | None = None,
                method: str = "sorted") -> Table:
    """Inner join; ``build`` keys must be unique among valid rows.

    Output = probe rows that matched (as a masked table — no compaction),
    plus ``take`` columns gathered from build.  Output capacity = probe
    capacity (FK->PK join never expands the probe side).
    """
    matched, bidx = _probe(probe_on, probe.valid_mask(), build, build_on,
                           index, method)
    cols = dict(probe.columns)
    with jax.named_scope(tracing.JOIN_TAKE):
        for name in take:
            if name in cols:
                raise ValueError(f"join output column collision: {name}")
            cols[name] = build[name][bidx]
    return Table(cols, matched.sum().astype(jnp.int32), matched)


def semi_join(probe: Table, build: Table, probe_on, build_on,
              index: BuildIndex | None = None, method: str = "sorted") -> Table:
    matched, _ = _probe(probe_on, probe.valid_mask(), build, build_on,
                        index, method)
    return Table(dict(probe.columns), matched.sum().astype(jnp.int32), matched)


def anti_join(probe: Table, build: Table, probe_on, build_on,
              index: BuildIndex | None = None, method: str = "sorted") -> Table:
    matched, _ = _probe(probe_on, probe.valid_mask(), build, build_on,
                        index, method)
    keep = ~matched & probe.valid_mask()
    return Table(dict(probe.columns), keep.sum().astype(jnp.int32), keep)


def left_join(probe: Table, build: Table, probe_on, build_on,
              take: Sequence[str], defaults: dict[str, float | int],
              index: BuildIndex | None = None, method: str = "sorted") -> Table:
    """Left outer join; unmatched probe rows take ``defaults``; adds ``__matched``."""
    matched, bidx = _probe(probe_on, probe.valid_mask(), build, build_on,
                           index, method)
    cols = dict(probe.columns)
    with jax.named_scope(tracing.JOIN_TAKE):
        for name in take:
            gathered = build[name][bidx]
            cols[name] = jnp.where(
                matched, gathered,
                jnp.asarray(defaults[name], dtype=gathered.dtype))
    cols["__matched"] = matched
    return Table(cols, probe.count, probe.valid)


# ---------------------------------------------------------------------------
# grouped aggregation
# ---------------------------------------------------------------------------

_MERGE_OP = {"sum": "sum", "count": "sum", "min": "min", "max": "max"}


def _agg_value(t: Table, values, cap: int) -> jax.Array:
    """Materialize an agg value spec (array | column name | None=ones)."""
    if values is None:
        return jnp.ones((cap,), dtype=jnp.int64)
    if isinstance(values, str):
        return t[values]
    return values


@tracing.op_scope(tracing.GROUP_BY)
def group_aggregate(t: Table, key_cols: Sequence[str],
                    aggs: Sequence[tuple[str, str, jax.Array | str | None]],
                    key_bits: Sequence[int] | None = None,
                    method: str = "auto", use_kernel: bool | None = None,
                    return_overflow: bool = False,
                    groups_hint: int | None = None,
                    hash_factor: float = 2.0):
    """Grouped aggregation; sortless when the key domain is provably small
    OR a distinct-group bound is claimed.

    Three execution paths, selected by ``method``:

      * ``"direct"`` — direct addressing: the packed key IS the dense group
        id (domain ``2^sum(key_bits)``, which must be <= 2^13), aggregates
        run through the ``kernels/segsum`` one-hot MXU reduce, and the dense
        slots compact to the front via a cumsum rank — ZERO sorts.  Scalar
        aggregation (no key columns) is the trivial domain-1 case.
      * ``"hash"`` — hash compaction for *data-dependent* domains: a
        trace-time on-device dictionary (``kernels/hash_group``,
        insert-or-lookup over two-plane 64-bit keys) of
        ``groups_hint * hash_factor`` slots maps each row to its slot, slots
        rank to ascending-key dense group ids by a sort-free O(cap^2)
        compare over the SMALL dictionary, and aggregates ride the same
        segsum one-hot reduce — ZERO sorts with no ``key_bits`` at all.
        Needs 1-2 key columns (the legacy collision-safe packing) and
        ``groups_hint <= HASH_AGG_GROUPS_MAX``; any int64 key values work,
        negatives included.
      * ``"sort"`` — the phase-1 engine: exactly ONE stable argsort whose
        order is reused for every aggregate (segment reductions).
      * ``"auto"`` (default) — direct when eligible, else hash when eligible,
        sort otherwise.

    aggs: (out_name, op, values) with op in {sum,count,min,max}; ``values`` is an
    array (an expression over t), a column name, or None for count.
    ``key_bits`` gives provable per-column bit widths (``0 <= t[k] < 2^bits``)
    so >2 key columns pack into the single int64 key (see ``combine_keys``)
    AND so the direct path can trust the domain bound.  Neither claim ever
    silently drops groups: a lying ``key_bits`` routes out-of-domain valid
    rows to the dead slot and raises the overflow flag; a dictionary that
    cannot place a row (full, or ``groups_hint`` undercounted the distinct
    groups) raises the same flag (``return_overflow=True`` returns
    ``(table, overflow)``; the backends feed it to the re-execution runner,
    whose capacity-factor escalation scales ``hash_factor`` and hence the
    dictionary).
    Output: key columns + agg columns; count = number of groups; group order
    is ascending packed key on all paths; capacity preserved
    (n_groups <= count <= capacity); output is compact.

    Rows past ``count`` are unspecified and differ between paths: notably a
    scalar min/max over ZERO valid rows leaves 0 at slot 0 on the direct
    path (matching the NumPy oracle's empty convention) but the reduction
    identity on the sort path — consumers must respect ``count``.
    """
    if use_kernel is None:
        use_kernel = agg_kernel_default()
    direct_ok = (not key_cols) or (
        key_bits is not None and sum(key_bits) <= DIRECT_AGG_BITS_MAX)
    hash_ok = bool(key_cols) and len(key_cols) <= 2 and \
        groups_hint is not None and groups_hint <= HASH_AGG_GROUPS_MAX
    if method == "auto":
        method = "direct" if direct_ok else ("hash" if hash_ok else "sort")
    if method == "direct":
        if not direct_ok:
            raise ValueError("group_aggregate: direct path needs key_bits "
                             f"with sum <= {DIRECT_AGG_BITS_MAX}")
        out, overflow = _group_aggregate_direct(t, key_cols, aggs, key_bits,
                                                use_kernel)
    elif method == "hash":
        if not hash_ok:
            raise ValueError("group_aggregate: hash path needs 1-2 key "
                             "columns and groups_hint <= "
                             f"{HASH_AGG_GROUPS_MAX}")
        out, overflow = _group_aggregate_hash(t, key_cols, aggs, groups_hint,
                                              hash_factor, use_kernel)
    elif method == "sort":
        out = _group_aggregate_sorted(t, key_cols, aggs, key_bits)
        overflow = jnp.asarray(False)
    else:
        raise ValueError(f"unknown group_aggregate method {method!r}")
    return (out, overflow) if return_overflow else out


def _reduce_aggs(t: Table, aggs, gid: jax.Array, dom: int, in_dom: jax.Array,
                 cnt: jax.Array, use_kernel: bool, cap: int
                 ) -> dict[str, jax.Array]:
    """Shared sortless reduction core (direct + hash paths): per-agg (dom,)
    arrays via the segsum kernel, with same-dtype sums batched into one
    multi-column call.  ``in_dom`` masks rows excluded from every aggregate
    (invalid, out-of-claimed-domain, unresolved); ``cnt`` is the group
    occupancy, which doubles as every count aggregate."""
    reduced: dict[str, jax.Array] = {}
    sum_batches: dict = {}
    for out_name, op, values in aggs:
        if op == "count":
            reduced[out_name] = cnt
            continue
        v = _agg_value(t, values, cap)
        if op == "sum":
            v = jnp.where(in_dom, v, jnp.zeros((), v.dtype))
            sum_batches.setdefault(jnp.dtype(v.dtype), []).append((out_name, v))
        elif op == "min":
            v = jnp.where(in_dom, v, _dtype_max(v.dtype))
            reduced[out_name] = _ss_ops.segment_reduce(
                gid, v, dom, op="min", use_kernel=use_kernel)
        elif op == "max":
            v = jnp.where(in_dom, v, _dtype_min(v.dtype))
            reduced[out_name] = _ss_ops.segment_reduce(
                gid, v, dom, op="max", use_kernel=use_kernel)
        else:
            raise ValueError(f"unknown agg op {op!r}")
    for dt, items in sum_batches.items():
        stacked = jnp.stack([v for _, v in items], axis=1)
        sums = _ss_ops.segment_reduce(gid, stacked, dom, op="sum",
                                      use_kernel=use_kernel)
        for i, (name, _) in enumerate(items):
            reduced[name] = sums[:, i]
    return reduced


def _group_aggregate_direct(t: Table, key_cols: Sequence[str], aggs,
                            key_bits: Sequence[int] | None,
                            use_kernel: bool) -> tuple[Table, jax.Array]:
    """Sortless path: dense gid = packed key; segsum kernel; cumsum compact."""
    cap = t.capacity
    valid = t.valid_mask()
    if key_cols:
        bits = list(key_bits)
        dom = 1 << sum(bits)
        key = combine_keys([t[k] for k in key_cols], bits=bits)
        # the bits claim is checked PER COLUMN: an oversized value in a
        # non-leading column would OR into its neighbor's bits and alias an
        # in-range packed key, corrupting a group without tripping a range
        # check on the packed key alone
        in_dom = valid
        for k, b in zip(key_cols, bits):
            c = t[k]
            in_dom = in_dom & (c >= 0) & (c < (1 << b))
    else:
        bits, dom = [], 1
        key = jnp.zeros((cap,), _I64)
        in_dom = valid
    overflow = jnp.any(in_dom != valid)      # a valid row broke the bits claim
    gid = jnp.where(in_dom, key, dom).astype(jnp.int32)   # dead slot = dom

    # group occupancy doubles as every count aggregate
    cnt = _ss_ops.segment_reduce(gid, None, dom, op="count",
                                 use_kernel=use_kernel)               # (dom,)
    nonempty = cnt > 0
    ngroups = nonempty.sum().astype(jnp.int32)
    # compact dense slots to the front WITHOUT a sort: cumsum rank preserves
    # ascending-key order, so the output matches the sorted path row for row
    dst = jnp.where(nonempty, jnp.cumsum(nonempty.astype(jnp.int32)) - 1, cap)

    def _scatter(dom_vals: jax.Array) -> jax.Array:
        return jnp.zeros((cap,), dom_vals.dtype).at[dst].set(dom_vals,
                                                             mode="drop")

    out: dict[str, jax.Array] = {}
    # key columns decode from the slot index (packing is lossless in-domain)
    shift = sum(bits)
    for k, b in zip(key_cols, bits):
        shift -= b
        dom_keys = (jnp.arange(dom, dtype=_I64) >> shift) & ((1 << b) - 1)
        out[k] = _scatter(dom_keys.astype(t[k].dtype))

    reduced = _reduce_aggs(t, aggs, gid, dom, in_dom, cnt, use_kernel, cap)
    for out_name, _, _ in aggs:
        out[out_name] = _scatter(reduced[out_name])
    return Table(out, ngroups), overflow


def _group_aggregate_hash(t: Table, key_cols: Sequence[str], aggs,
                          groups_hint: int, hash_factor: float,
                          use_kernel: bool) -> tuple[Table, jax.Array]:
    """Hash-compaction path: trace-time dictionary -> ascending-key dense gid
    -> segsum kernel.  Zero sorts without provable key widths.

    The dictionary holds exact 64-bit keys (no domain claim to check), so the
    only failure modes are capacity-shaped: a row the dictionary cannot place
    (full, or an improbable probe-cluster) or more distinct groups than
    ``groups_hint`` claimed.  Both raise the overflow flag; the fault
    runner's escalation scales ``hash_factor`` (hence the dictionary), and
    an undercounting hint falls to its hint-drop recompilation — unplaced
    rows are EXCLUDED from every aggregate, never misassigned, so in-domain
    groups stay exact even on a flagged run (the lying-``key_bits``
    discipline, unchanged)."""
    cap = t.capacity
    valid = t.valid_mask()
    # legacy collision-safe packing (1-2 columns) — no width claims needed;
    # slots compare full 64-bit keys, so any int64 values group exactly
    key = combine_keys([t[k] for k in key_cols])
    dcap = _hg_ops.dict_capacity(groups_hint, hash_factor)
    slot, dkeys, occupied, unresolved = _hg_ops.build_group_dict(
        key, valid, dcap, use_kernel=use_kernel)
    rank = _hg_ops.dict_rank(dkeys, occupied)            # dcap for empty slots
    ngroups = occupied.sum().astype(jnp.int32)
    overflow = unresolved | (ngroups > groups_hint)
    resolved = valid & (slot >= 0)
    # gid IS the final output row (ascending packed key), so the reduced
    # arrays need no compaction scatter; dead slot = dcap (segsum convention)
    gid = jnp.where(resolved, rank[jnp.maximum(slot, 0)],
                    dcap).astype(jnp.int32)

    def _fit(dom_vals: jax.Array) -> jax.Array:
        if dcap >= cap:
            return dom_vals[:cap]
        return jnp.zeros((cap,), dom_vals.dtype).at[:dcap].set(dom_vals)

    out: dict[str, jax.Array] = {}
    # key columns scatter from the rows themselves (all rows of a group share
    # the value, duplicate writes are benign) — no packed-key decode, so the
    # path handles keys the bits-packing could not describe
    gid_drop = jnp.where(resolved, gid, cap)
    for k in key_cols:
        out[k] = jnp.zeros((cap,), t[k].dtype).at[gid_drop].set(
            t[k], mode="drop")
    cnt = _ss_ops.segment_reduce(gid, None, dcap, op="count",
                                 use_kernel=use_kernel)
    reduced = _reduce_aggs(t, aggs, gid, dcap, resolved, cnt, use_kernel, cap)
    for out_name, _, _ in aggs:
        out[out_name] = _fit(reduced[out_name])
    return Table(out, ngroups), overflow


def _group_aggregate_sorted(t: Table, key_cols: Sequence[str], aggs,
                            key_bits: Sequence[int] | None = None) -> Table:
    """Sort-based path: exactly ONE stable argsort, whose order is reused for
    every aggregate (segment reductions over the same segments)."""
    cap = t.capacity
    key = _valid_key(t, combine_keys([t[k] for k in key_cols], bits=key_bits)) \
        if key_cols else \
        jnp.where(t.valid_mask(), jnp.int64(0), KEY_SENTINEL)
    order = jnp.argsort(key)
    sk = key[order]
    valid = sk != KEY_SENTINEL
    first = jnp.concatenate([valid[:1], (sk[1:] != sk[:-1]) & valid[1:]])
    gid = jnp.cumsum(first.astype(jnp.int32)) - 1           # 0-based group id
    ngroups = first.sum().astype(jnp.int32)
    # invalid rows route to segment cap-1 which is provably not a valid group
    # whenever any invalid row exists (ngroups <= count <= cap-1); see tests.
    seg = jnp.where(valid, gid, cap - 1)

    out: dict[str, jax.Array] = {}
    for k in key_cols:
        v = t[k][order]
        fill = jnp.zeros((), v.dtype)
        # scatter-set: all rows of a group share the key value, so duplicate
        # writes are benign; invalid rows write the fill value into slot cap-1.
        out[k] = jnp.zeros((cap,), v.dtype).at[seg].set(jnp.where(valid, v, fill),
                                                        mode="drop")
    for out_name, op, values in aggs:
        v = _agg_value(t, values, cap)[order]
        if op == "count":
            v = jnp.where(valid, 1, 0).astype(jnp.int64)
            out[out_name] = jax.ops.segment_sum(v, seg, num_segments=cap,
                                                indices_are_sorted=True)
        elif op == "sum":
            v = jnp.where(valid, v, jnp.zeros((), v.dtype))
            out[out_name] = jax.ops.segment_sum(v, seg, num_segments=cap,
                                                indices_are_sorted=True)
        elif op == "min":
            big = _dtype_max(v.dtype)
            v = jnp.where(valid, v, big)
            out[out_name] = jax.ops.segment_min(v, seg, num_segments=cap,
                                                indices_are_sorted=True)
        elif op == "max":
            small = _dtype_min(v.dtype)
            v = jnp.where(valid, v, small)
            out[out_name] = jax.ops.segment_max(v, seg, num_segments=cap,
                                                indices_are_sorted=True)
        else:
            raise ValueError(f"unknown agg op {op!r}")
    return Table(out, ngroups)


def _dtype_max(dt):
    return jnp.asarray(np.inf if jnp.issubdtype(dt, jnp.floating) else np.iinfo(dt).max, dt)


def _dtype_min(dt):
    return jnp.asarray(-np.inf if jnp.issubdtype(dt, jnp.floating) else np.iinfo(dt).min, dt)


# ---------------------------------------------------------------------------
# ordering
# ---------------------------------------------------------------------------

def _order_operands(t: Table, keys: Sequence[tuple[str, bool]]
                    ) -> list[jax.Array]:
    """ORDER BY keys as ascending sort operands; invalid rows get sentinels
    in every operand, so they sort behind every valid row."""
    valid = t.valid_mask()
    operands = []
    for col, asc in keys:
        k = t[col]
        if jnp.issubdtype(k.dtype, jnp.floating):
            k = jnp.where(valid, k if asc else -k, np.inf)
        else:
            k = k.astype(_I64)
            k = jnp.where(valid, k if asc else -k, KEY_SENTINEL)
        operands.append(k)
    return operands


@tracing.op_scope(tracing.ORDER)
def sort_by(t: Table, keys: Sequence[tuple[str, bool]]) -> Table:
    """ORDER BY; keys = [(column, ascending)], first key most significant.

    A stable lexicographic order over all key columns at once; invalid rows
    sink to the back via sentinels in every key operand, so the output is
    compact.  ONE stable multi-operand ``lax.sort``, except on TPU up to
    ``PAIRWISE_ORDER_MAX`` rows, where each row's place is counted pairwise
    (``_pairwise_order``) and nothing is sorted.
    """
    operands = _order_operands(t, keys)
    if t.capacity <= PAIRWISE_ORDER_MAX:
        order = jax.lax.platform_dependent(
            *operands, tpu=_pairwise_order, default=_sort_order)
    else:
        order = _sort_order(*operands)
    return Table({k: v[order] for k, v in t.columns.items()}, t.count)


def _sort_order(*operands: jax.Array) -> jax.Array:
    """Row positions in the stable lexicographic order of ``operands``."""
    iota = jnp.arange(operands[0].shape[0], dtype=jnp.int32)
    return jax.lax.sort(operands + (iota,), num_keys=len(operands),
                        is_stable=True)[-1]


def _pairwise_order(*operands: jax.Array) -> jax.Array:
    """``_sort_order`` without a sort: each row goes to its stable rank."""
    iota = jnp.arange(operands[0].shape[0], dtype=jnp.int32)
    return jnp.zeros_like(iota).at[_stable_ranks(operands)].set(iota)


def _stable_ranks(operands: Sequence[jax.Array]) -> jax.Array:
    """Each row's position in the stable lexicographic order of the key
    ``operands``: the rows that compare smaller, or equal and earlier.
    Blocks of rows against all rows, so a tile holds ~2^22 compares."""
    cap = operands[0].shape[0]
    blk = max(1, min(cap, (1 << 22) // cap))
    pos = jnp.arange(cap, dtype=jnp.int32)

    def block_ranks(start):
        rows = jnp.minimum(start + jnp.arange(blk, dtype=jnp.int32), cap - 1)
        before = pos[None, :] < rows[:, None]          # equal and earlier
        for k in reversed(operands):                   # least significant 1st
            kr, ks = k[rows][:, None], k[None, :]
            before = (ks < kr) | ((ks == kr) & before)
        return jnp.sum(before, axis=1, dtype=jnp.int32)

    starts = jnp.arange(0, cap, blk, dtype=jnp.int32)
    return jax.lax.map(block_ranks, starts).reshape(-1)[:cap]


@tracing.op_scope(tracing.ORDER)
def sort_limit(t: Table, keys: Sequence[tuple[str, bool]], n: int) -> Table:
    """ORDER BY ... LIMIT n: ``limit(sort_by(t, keys), n)``, row for row.

    On TPU, below the capacity, it sorts nothing: n rounds each pick the
    smallest row not yet taken, earliest position first
    (``_smallest_rows``).  XLA:TPU spends minutes compiling a multi-operand
    64-bit sort of 10^5+ rows; a LIMIT needs n.
    """
    if n >= t.capacity:
        return limit(sort_by(t, keys), n)
    order = jax.lax.platform_dependent(
        *_order_operands(t, keys),
        tpu=lambda *ops: _smallest_rows(ops, n),
        default=lambda *ops: _sort_order(*ops)[:n])
    return Table({k: v[order] for k, v in t.columns.items()},
                 jnp.minimum(t.count, n).astype(jnp.int32))


def _smallest_rows(operands: Sequence[jax.Array], n: int) -> jax.Array:
    """Positions of the ``n`` lexicographically smallest rows of the key
    ``operands``, in order, ties by position (a stable sort's first n)."""
    cap = operands[0].shape[0]

    def pick(i, carry):
        taken, order = carry
        cand = ~taken
        for k in operands:
            m = jnp.min(jnp.where(cand, k, _dtype_max(k.dtype)))
            cand = cand & (k == m)
        row = jnp.argmax(cand).astype(jnp.int32)    # first candidate
        return taken.at[row].set(True), order.at[i].set(row)

    _, order = jax.lax.fori_loop(
        0, n, pick, (jnp.zeros((cap,), bool), jnp.zeros((n,), jnp.int32)))
    return order
