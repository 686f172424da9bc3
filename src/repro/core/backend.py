"""Execution backends for tensor query plans.

Queries are written ONCE against the :class:`Context` API and run on three
engines:

  * :class:`RefContext`   — NumPy oracle / CPU baseline (exact shapes).
  * :class:`LocalContext` — single-device JAX, static shapes, no exchanges.
  * :class:`DistContext`  — SPMD under ``shard_map``; exchange operators are
    real mesh collectives (the paper's distributed TQP model §2.4: every
    process runs the same tensor program on its partition, no driver).

Exchange placement is explicit in query code (``ctx.shuffle`` / ``ctx.broadcast``
/ ``exchange=`` on group_by) — mirroring the paper's manually-optimized tensor
programs (§4.4) — and is counted identically on every backend so plan statistics
(paper Table 4) can be produced without a cluster.

``join_method`` selects the per-device join engine on the JAX backends:
``"sorted"`` (searchsorted probe, always available) or ``"hash"`` (Pallas
bucket-table probe); both paths are byte-identical (tests/test_sort_tax.py)
and share the per-plan build-side cache on ``_BaseContext``.  Under
``"sorted"``, a join whose build key the planner proved to lie in a dense
``key_range`` takes the direct-address index instead (one gather per probe
row, ``rel.direct_join_fits``), with the same answers.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import compat
from . import exchange as ex
from . import reference as ref
from . import relational as rel
from . import wire as wi
from .table import Database, Table, from_numpy, to_numpy

__all__ = [
    "PlanStats", "RefContext", "LocalContext", "DistContext",
    "run_reference", "run_local", "run_distributed",
    "partition_database", "hash_partition_np",
]

AggSpec = Sequence[tuple]  # (out_name, op, col | callable | None)

_YEAR_LUT = None


def _year_lut() -> np.ndarray:
    """epoch-day -> calendar year, for days 1970-01-01 .. 2005-12-31."""
    global _YEAR_LUT
    if _YEAR_LUT is None:
        d = np.arange(0, 13150).astype("timedelta64[D]") + np.datetime64("1970-01-01")
        _YEAR_LUT = d.astype("datetime64[Y]").astype(np.int64) + 1970
    return _YEAR_LUT


@dataclasses.dataclass
class PlanStats:
    shuffles: int = 0
    broadcasts: int = 0
    final_gathers: int = 0
    allreduces: int = 0
    overflow_checks: int = 0
    log: list = dataclasses.field(default_factory=list)
    # join indexes built, by the method each took: direct / sorted / hash
    index_builds: dict = dataclasses.field(default_factory=dict)

    def counts(self):
        return {"shuffles": self.shuffles, "broadcasts": self.broadcasts,
                "final_gathers": self.final_gathers, "allreduces": self.allreduces}


def _eval_aggs(ctx, t, aggs):
    """Materialize callable agg expressions into arrays."""
    out = []
    for name, op, v in aggs:
        if callable(v):
            v = v(t)
        out.append((name, op, v))
    return out


def _expand_avg(aggs):
    """avg -> (sum, count) pairs + postprocessing recipe."""
    expanded, post = [], []
    for name, op, v in aggs:
        if op == "avg":
            expanded.append((f"__{name}_s", "sum", v))
            expanded.append((f"__{name}_c", "count", None))
            post.append(name)
        else:
            expanded.append((name, op, v))
    return expanded, post


_MERGE = {"sum": "sum", "count": "sum", "min": "min", "max": "max"}


class _BaseContext:
    """Shared bookkeeping + derived helpers.

    ``_join_cache`` is the per-query build-side cache: a (build table, key)
    pair is indexed (direct, sorted or bucket-hashed) at most once per plan,
    however many joins probe it — dimension tables stop paying one build
    per join.  The cache holds a strong reference to the build table so
    ``id()`` keys stay unique for the context's (= one plan's) lifetime.
    """

    join_method = "sorted"  # "sorted" (searchsorted) | "hash" (Pallas probe)

    def __init__(self, db: Database, capacity_factor: float = 2.0,
                 wire_format: str | None = None):
        self.db = db
        self.dicts = db.dicts
        self.stats = PlanStats()
        self.capacity_factor = capacity_factor
        self.wire_format = wire_format or wi.wire_default()
        self._join_cache: dict[tuple, tuple] = {}

    @property
    def wire_narrow(self) -> bool:
        return self.wire_format == "narrow"

    def _wire_entry(self, kind: str, t, wire, narrow: bool | None = None,
                    ) -> ex.ExchangeStats:
        """Trace-time per-row wire descriptor of an exchange payload.

        Every backend logs one of these per exchange — the non-distributed
        backends with the per-row fields only — so the IR-derived static
        report (``planner.static_wire_stats``) can be asserted equal to
        runtime stats on all three engines."""
        names = sorted(t) if isinstance(t, dict) else t.names
        dtypes = {n: np.dtype(t[n].dtype) for n in names}
        if narrow is None:
            narrow = self.wire_narrow
        fmt = wi.plan_wire_format(names, dtypes, bounds=wire, narrow=narrow)
        return ex.ExchangeStats(
            kind=kind, participants=1, message_bytes=0, total_bytes=0,
            collectives=0, row_wire_bytes=fmt.row_wire_bytes,
            row_logical_bytes=fmt.row_logical_bytes,
            wire="narrow" if fmt.narrow else "wide")

    def bucket_cap(self) -> int:
        """Per-bucket capacity of the Pallas hash-join table, scaled by the
        runner's capacity factor: the default factor (2.0) gives the historic
        cap of 16, and the fault runner's escalation (factor *= 2 on
        overflow) genuinely enlarges the buckets on re-execution instead of
        retrying the same doomed layout (ROADMAP open item)."""
        return max(2, int(round(8 * self.capacity_factor)))

    # -- dictionary-encoded string predicates (TQP-style) ------------------
    def str_lookup(self, col: str, pred: Callable[[np.ndarray], np.ndarray]):
        """Host-evaluated predicate over dictionary -> per-row boolean."""
        return self.db.dict_mask(col, pred)

    def like(self, t, col: str, *substrings: str):
        """col LIKE '%a%b%' -> ordered substring match on the dictionary."""
        def pred(d):
            m = np.ones(len(d), dtype=bool)
            for i, s in enumerate(d):
                pos = 0
                ok = True
                for sub in substrings:
                    j = s.find(sub, pos)
                    if j < 0:
                        ok = False
                        break
                    pos = j + len(sub)
                m[i] = ok
            return m
        lut = self.xp.asarray(self.str_lookup(col, pred))
        return lut[t[col]]

    def rename(self, t, mapping: dict):
        if isinstance(t, dict):
            return {mapping.get(k, k): v for k, v in t.items()}
        return t.rename(mapping)

    def starts_with(self, t, col: str, prefix: str):
        lut = self.xp.asarray(self.str_lookup(
            col, lambda d: np.char.startswith(d.astype(str), prefix)))
        return lut[t[col]]

    def ends_with(self, t, col: str, suffix: str):
        lut = self.xp.asarray(self.str_lookup(
            col, lambda d: np.char.endswith(d.astype(str), suffix)))
        return lut[t[col]]

    def alpha_rank(self, t, col: str):
        """Alphabetical rank of a dictionary-encoded column (for ORDER BY on
        strings: code order != lexicographic order)."""
        d = self.dicts[col]
        rank = np.empty(len(d), dtype=np.int64)
        rank[np.argsort(d)] = np.arange(len(d))
        return self.xp.asarray(rank)[t[col]]

    def dict_bits(self, col: str) -> int:
        """Provable bit width of a dictionary-encoded column: codes lie in
        ``[0, len(dict))``, so ``ceil(log2(len(dict)))`` bits bound the domain
        — the host-side fact plans cite in ``key_bits=`` to unlock the
        sortless direct-addressing group-by (see queries/__init__.py)."""
        return max(1, math.ceil(math.log2(max(2, len(self.dicts[col])))))

    _YEAR_BASE = 0  # epoch day 0

    def year(self, t_or_col, col: str | None = None):
        """Extract calendar year from an epoch-days column via a host LUT."""
        v = t_or_col[col] if col is not None else t_or_col
        lut = _year_lut()
        return self.xp.asarray(lut)[v]

    def isin(self, t, col: str, values: Sequence[str]):
        codes = self.db.codes(col, values)
        x = t[col]
        m = self.xp.zeros(x.shape, dtype=bool)
        for c in codes:
            m = m | (x == c)
        return m

    def eq(self, t, col: str, value: str):
        return t[col] == self.db.code(col, value)

    # -- exchange bookkeeping ----------------------------------------------
    def _count(self, kind: str, stats=None):
        if kind == "shuffle":
            self.stats.shuffles += 1
        elif kind in ("broadcast", "broadcast_p2p"):
            self.stats.broadcasts += 1
        elif kind == "gather":
            self.stats.final_gathers += 1
        elif kind == "allreduce":
            self.stats.allreduces += 1
        if stats is not None:
            self.stats.log.append(stats)

    # -- chaos fault injection ---------------------------------------------
    # a ChaosInjector (distributed/chaos.py), attached by the run_* drivers;
    # None (the default) makes every cut point a no-op
    chaos = None

    def _chaos_point(self, cut: str, tamperable: bool = False):
        """Named failure-domain cut point (scan / exchange / group_by /
        finalize).  Asks the armed injector for a fault due here this
        attempt: TRANSIENT/DETERMINISTIC faults raise (aborting the trace),
        STRAGGLER sleeps, OVERFLOW ORs the traced ``ctx.overflow`` flag, and
        CORRUPT returns a payload tamper callable when the call site can
        route it into a checksummed exchange (``tamperable``) — otherwise it
        ORs ``ctx.corrupt`` directly, simulating the detection."""
        if self.chaos is None:
            return None
        return self.chaos.fire(cut, self, tamperable=tamperable)


# ===========================================================================
# NumPy reference backend
# ===========================================================================

class RefContext(_BaseContext):
    xp = np
    distributed = False

    def scan(self, name):
        return dict(self.db.tables[name])  # RTable = dict of np arrays

    def filter(self, t, mask):
        return ref.filter_rows(t, np.asarray(mask))

    def with_col(self, t, **exprs):
        out = dict(t)
        for k, fn in exprs.items():
            out[k] = fn(t) if callable(fn) else fn
        return out

    def select(self, t, *names):
        return {n: t[n] for n in names}

    def _key(self, t, on):
        if isinstance(on, str):
            return t[on]
        return ref.combine_keys([t[c] for c in on])

    # key_range is a JAX-engine index hint; the oracle ignores it
    def join(self, probe, build, probe_on, build_on, take, key_range=None):
        return ref.join_unique(probe, build, self._key(probe, probe_on),
                               self._key(build, build_on), take)

    def semi(self, probe, build, probe_on, build_on, key_range=None):
        return ref.semi_join(probe, build, self._key(probe, probe_on),
                             self._key(build, build_on))

    def anti(self, probe, build, probe_on, build_on, key_range=None):
        return ref.anti_join(probe, build, self._key(probe, probe_on),
                             self._key(build, build_on))

    def left(self, probe, build, probe_on, build_on, take, defaults,
             key_range=None):
        return ref.left_join(probe, build, self._key(probe, probe_on),
                             self._key(build, build_on), take, defaults)

    def group_by(self, t, keys, aggs, exchange="local", final=False,
                 groups_hint=None, key_bits=None, wire=None, method="auto"):
        # key_bits / method are JAX-engine planning hints; the oracle ignores
        # them (np.unique-based group-by regardless of path)
        aggs, avg_post = _expand_avg(list(aggs))
        out = ref.group_aggregate(t, keys, _eval_aggs(self, t, aggs))
        # the exchange (were this distributed) moves the expanded partial —
        # the entry is logged AFTER agg-expression scalar sub-queries ran,
        # matching the distributed backend's partial-then-exchange order
        if exchange == "shuffle":
            self._count("shuffle", self._wire_entry("shuffle", out, wire))
        elif exchange == "gather":
            kind = "gather" if final else "broadcast"
            self._count(kind, self._wire_entry(kind, out, wire))
        for name in avg_post:
            out[name] = out[f"__{name}_s"] / np.maximum(out[f"__{name}_c"], 1)
            del out[f"__{name}_s"], out[f"__{name}_c"]
        return out

    def agg_scalar(self, t, aggs):
        self._count("allreduce")
        aggs, avg_post = _expand_avg(list(aggs))
        g = ref.group_aggregate(t, [], _eval_aggs(self, t, aggs))
        out = {k: (v[0] if len(v) else np.asarray(0.0)) for k, v in g.items()}
        for name in avg_post:
            out[name] = out[f"__{name}_s"] / max(out[f"__{name}_c"], 1)
            del out[f"__{name}_s"], out[f"__{name}_c"]
        return out

    def shuffle(self, t, key, wire=None):
        self._count("shuffle", self._wire_entry("shuffle", t, wire))
        return t

    def broadcast(self, t, p2p=False, wire=None):
        kind = "broadcast_p2p" if p2p else "broadcast"
        # the p2p variant is the §7.1 baseline and deliberately stays wide
        self._count(kind, self._wire_entry(kind, t, wire,
                                           narrow=False if p2p else None))
        return t

    def shrink(self, t, cap):
        self.stats.overflow_checks += 1
        return t

    def finalize(self, t, sort_keys=None, limit=None, replicated=False,
                 wire=None):
        if not replicated:
            self._count("gather", self._wire_entry("gather", t, wire))
        if sort_keys:
            t = ref.sort_by(t, sort_keys)
        if limit is not None:
            t = ref.limit(t, limit)
        return t

    def nrows(self, t):
        return len(next(iter(t.values())))


# ===========================================================================
# Single-device JAX backend (static shapes, exchanges are identity)
# ===========================================================================

class LocalContext(_BaseContext):
    xp = jnp
    distributed = False

    def __init__(self, db, tables: dict[str, Table], capacity_factor=2.0,
                 join_method: str = "sorted", use_kernel: bool | None = None,
                 wire_format: str | None = None):
        super().__init__(db, capacity_factor, wire_format)
        self._tables = tables
        self.overflow = jnp.asarray(False)
        self.corrupt = jnp.asarray(False)
        self.join_method = join_method
        # use_kernel=False runs aggregation/dispatch through the jnp oracle
        # (the CI matrix leg); None -> REPRO_AGG_KERNEL env default
        self.use_kernel = rel.agg_kernel_default() if use_kernel is None \
            else use_kernel

    def scan(self, name):
        self._chaos_point("scan")
        return self._tables[name]

    def filter(self, t, mask):
        return rel.filter_rows(t, mask)

    def with_col(self, t, **exprs):
        return t.replace(**{k: (fn(t) if callable(fn) else fn)
                            for k, fn in exprs.items()})

    def select(self, t, *names):
        return t.select(*names)

    def _key(self, t, on):
        if isinstance(on, str):
            return t[on]
        return rel.combine_keys([t[c] for c in on])

    def _build_index(self, build, build_on, key_range=None) -> rel.BuildIndex:
        """Per-plan build cache: index each (build table, key) pair once.

        ``key_range`` is the planner's proven ``(lo, hi)`` of the build key;
        with it the sorted engine builds the direct-address index (which
        falls back to sorted where the domain is too sparse)."""
        if isinstance(build_on, str):
            ck = (id(build), build_on, key_range)
        elif isinstance(build_on, (list, tuple)) and \
                all(isinstance(c, str) for c in build_on):
            ck = (id(build), tuple(build_on), key_range)
        else:  # raw key arrays etc. — build fresh rather than key by id()
            ck = None
        hit = self._join_cache.get(ck) if ck is not None else None
        if hit is not None:
            return hit[1]
        method = self.join_method
        if method == "sorted" and key_range is not None:
            method = "direct"
        idx = rel.build_index(build, self._key(build, build_on),
                              method=method, bucket_cap=self.bucket_cap(),
                              key_range=key_range)
        self.overflow = self.overflow | idx.overflow
        builds = self.stats.index_builds
        builds[idx.method] = builds.get(idx.method, 0) + 1
        if ck is not None:
            self._join_cache[ck] = (build, idx)  # keep build alive: id()
        return idx

    def join(self, probe, build, probe_on, build_on, take, key_range=None):
        return rel.join_unique(probe, build, self._key(probe, probe_on),
                               self._key(build, build_on), take,
                               index=self._build_index(build, build_on,
                                                       key_range))

    def semi(self, probe, build, probe_on, build_on, key_range=None):
        return rel.semi_join(probe, build, self._key(probe, probe_on),
                             self._key(build, build_on),
                             index=self._build_index(build, build_on,
                                                     key_range))

    def anti(self, probe, build, probe_on, build_on, key_range=None):
        return rel.anti_join(probe, build, self._key(probe, probe_on),
                             self._key(build, build_on),
                             index=self._build_index(build, build_on,
                                                     key_range))

    def left(self, probe, build, probe_on, build_on, take, defaults,
             key_range=None):
        return rel.left_join(probe, build, self._key(probe, probe_on),
                             self._key(build, build_on), take, defaults,
                             index=self._build_index(build, build_on,
                                                     key_range))

    def group_by(self, t, keys, aggs, exchange="local", final=False,
                 groups_hint=None, key_bits=None, wire=None, method="auto"):
        """``method`` selects the aggregation path (planner rule: ``hash``
        when ``groups_hint`` is claimed but ``key_bits`` is unprovable);
        the dictionary capacity scales with the runner's capacity factor so
        escalation genuinely enlarges it on re-execution."""
        self._chaos_point("group_by")
        aggs, avg_post = _expand_avg(list(aggs))
        out, ov = rel.group_aggregate(t, keys, _eval_aggs(self, t, aggs),
                                      key_bits=key_bits, method=method,
                                      groups_hint=groups_hint,
                                      hash_factor=self.capacity_factor,
                                      use_kernel=self.use_kernel,
                                      return_overflow=True)
        self.overflow = self.overflow | ov
        if groups_hint is not None:
            out, ov = rel.static_shrink(out, min(out.capacity, groups_hint))
            self.overflow = self.overflow | ov
        # log after the partial (and its agg-expression sub-queries), in the
        # same position the distributed engine issues the real exchange
        if exchange == "shuffle":
            self._count("shuffle", self._wire_entry("shuffle", out, wire))
        elif exchange == "gather":
            kind = "gather" if final else "broadcast"
            self._count(kind, self._wire_entry(kind, out, wire))
        for name in avg_post:
            cnt = jnp.maximum(out[f"__{name}_c"], 1)
            out = out.replace(**{name: out[f"__{name}_s"] / cnt})
            out = out.drop(f"__{name}_s", f"__{name}_c")
        return out

    def agg_scalar(self, t, aggs):
        self._chaos_point("group_by")   # scalar aggregation = group_by domain
        self._count("allreduce")
        aggs, avg_post = _expand_avg(list(aggs))
        g = rel.group_aggregate(t, [], _eval_aggs(self, t, aggs),
                                use_kernel=self.use_kernel)
        out = {name: g[name][0] for name in g.names}
        for name in avg_post:
            out[name] = out[f"__{name}_s"] / jnp.maximum(out[f"__{name}_c"], 1)
            del out[f"__{name}_s"], out[f"__{name}_c"]
        return out

    def shuffle(self, t, key, wire=None):
        self._chaos_point("exchange")
        self._count("shuffle", self._wire_entry("shuffle", t, wire))
        return t

    def broadcast(self, t, p2p=False, wire=None):
        self._chaos_point("exchange")
        kind = "broadcast_p2p" if p2p else "broadcast"
        self._count(kind, self._wire_entry(kind, t, wire,
                                           narrow=False if p2p else None))
        return t

    def shrink(self, t, cap):
        self.stats.overflow_checks += 1
        t, ov = rel.static_shrink(t, cap)
        self.overflow = self.overflow | ov
        return t

    def finalize(self, t, sort_keys=None, limit=None, replicated=False,
                 wire=None):
        self._chaos_point("finalize")
        if not replicated:
            self._count("gather", self._wire_entry("gather", t, wire))
        return _order_limit(t, sort_keys, limit)

    def nrows(self, t):
        return t.count


# ===========================================================================
# Distributed backend (inside shard_map)
# ===========================================================================

class DistContext(LocalContext):
    """SPMD execution: exchange calls become real collectives."""
    distributed = True

    def __init__(self, db, tables, axis_name: str, num_partitions: int,
                 capacity_factor=2.0, packed_exchange=True,
                 join_method: str = "sorted", use_kernel: bool | None = None,
                 wire_format: str | None = None):
        super().__init__(db, tables, capacity_factor, join_method, use_kernel,
                         wire_format)
        self.axis = axis_name
        self.N = num_partitions
        self.packed = packed_exchange

    # -- exchanges ----------------------------------------------------------
    def shuffle(self, t, key, dest_ids=None, wire=None):
        tamper = self._chaos_point("exchange", tamperable=self.packed)
        self._count("shuffle")
        keyv = t[key] if isinstance(key, str) else self._key(t, key)
        cap_per_dest = max(8, math.ceil(t.capacity * self.capacity_factor / self.N))
        out, ov, cr, _, stats = ex.shuffle(t, keyv, self.axis, self.N,
                                           cap_per_dest,
                                           packed=self.packed, dest_ids=dest_ids,
                                           use_kernel=self.use_kernel,
                                           wire=wire, narrow=self.wire_narrow,
                                           tamper=tamper)
        self.stats.log.append(stats)
        self.overflow = self.overflow | ov
        self.corrupt = self.corrupt | cr
        return out

    def broadcast(self, t, p2p=False, wire=None):
        # the p2p baseline ships unchecked — corrupt faults here are simulated
        tamper = self._chaos_point("exchange",
                                   tamperable=self.packed and not p2p)
        self._count("broadcast_p2p" if p2p else "broadcast")
        if p2p:
            out, stats = ex.broadcast_table_p2p(t, self.axis, self.N)
        else:
            out, ov, cr, stats = ex.broadcast_table(t, self.axis, self.N,
                                                    packed=self.packed,
                                                    wire=wire,
                                                    narrow=self.wire_narrow,
                                                    tamper=tamper)
            self.overflow = self.overflow | ov
            self.corrupt = self.corrupt | cr
        self.stats.log.append(stats)
        return out

    # -- distributed aggregation --------------------------------------------
    def group_by(self, t, keys, aggs, exchange="local", final=False,
                 groups_hint=None, key_bits=None, wire=None, method="auto"):
        """groups_hint: static bound on distinct groups (e.g. a dictionary
        domain) — shrinks the partial aggregate BEFORE the exchange, so a
        gather/shuffle of a wide scan's partial moves O(groups), not
        O(scan capacity).  Overflow feeds the re-execution runner.
        key_bits: provable per-column key bit widths — both the per-device
        partial and the post-exchange merge run the sortless direct path.
        method: aggregation path; ``hash`` (groups_hint claimed, key_bits
        unprovable — the Q13 shape) builds a per-device dictionary sized by
        the capacity factor, and the SAME method runs the post-exchange
        merge, so both sides of the exchange stay sortless.
        wire: provable (lo, hi) bounds per partial column — the exchange
        ships the partial at its inferred lane widths."""
        tamper = self._chaos_point(
            "group_by", tamperable=self.packed and exchange != "local")
        aggs, avg_post = _expand_avg(list(aggs))
        partial, ov = rel.group_aggregate(t, keys, _eval_aggs(self, t, aggs),
                                          key_bits=key_bits, method=method,
                                          groups_hint=groups_hint,
                                          hash_factor=self.capacity_factor,
                                          use_kernel=self.use_kernel,
                                          return_overflow=True)
        self.overflow = self.overflow | ov
        if groups_hint is not None:
            partial, ov = rel.static_shrink(
                partial, min(partial.capacity, groups_hint))
            self.overflow = self.overflow | ov
        if exchange == "local":
            out = partial
        else:
            merge = [(name, _MERGE[op], name) for name, op, _ in aggs]
            if exchange == "shuffle":
                self._count("shuffle")
                keyv = rel.combine_keys([partial[k] for k in keys],
                                        bits=key_bits) if len(keys) > 1 \
                    else partial[keys[0]]
                cap_per_dest = max(8, math.ceil(
                    partial.capacity * self.capacity_factor / self.N))
                moved, ov, cr, _, stats = ex.shuffle(partial, keyv, self.axis,
                                                     self.N, cap_per_dest,
                                                     packed=self.packed,
                                                     use_kernel=self.use_kernel,
                                                     wire=wire,
                                                     narrow=self.wire_narrow,
                                                     tamper=tamper)
                self.stats.log.append(stats)
                self.overflow = self.overflow | ov
                self.corrupt = self.corrupt | cr
            elif exchange == "gather":
                kind = "gather" if final else "broadcast"
                self._count(kind)
                moved, ov, cr, stats = ex.broadcast_table(
                    partial, self.axis, self.N, packed=self.packed,
                    wire=wire, narrow=self.wire_narrow, tamper=tamper)
                self.overflow = self.overflow | ov
                self.corrupt = self.corrupt | cr
                self.stats.log.append(dataclasses.replace(stats, kind=kind))
            else:
                raise ValueError(exchange)
            # the partial->global merge reuses the same provable widths (or
            # the same dictionary bound), so a hinted group-by is sortless on
            # BOTH sides of the exchange
            out, ov = rel.group_aggregate(moved, keys, merge,
                                          key_bits=key_bits, method=method,
                                          groups_hint=groups_hint,
                                          hash_factor=self.capacity_factor,
                                          use_kernel=self.use_kernel,
                                          return_overflow=True)
            self.overflow = self.overflow | ov
        for name in avg_post:
            cnt = jnp.maximum(out[f"__{name}_c"], 1)
            out = out.replace(**{name: out[f"__{name}_s"] / cnt})
            out = out.drop(f"__{name}_s", f"__{name}_c")
        return out

    def agg_scalar(self, t, aggs):
        self._chaos_point("group_by")   # allreduce ships unchecked scalars:
        self._count("allreduce")        # corrupt faults here are simulated
        aggs, avg_post = _expand_avg(list(aggs))
        g = rel.group_aggregate(t, [], _eval_aggs(self, t, aggs),
                                use_kernel=self.use_kernel)
        partials = {name: g[name][0] for name in g.names}
        ops = {name: _MERGE[op] for name, op, _ in aggs}
        out = ex.partial_to_global(partials, ops, self.axis)
        for name in avg_post:
            out[name] = out[f"__{name}_s"] / jnp.maximum(out[f"__{name}_c"], 1)
            del out[f"__{name}_s"], out[f"__{name}_c"]
        return out

    def finalize(self, t, sort_keys=None, limit=None, replicated=False,
                 wire=None):
        """Final result collection: local order/limit, gather, global order.

        ``replicated=True`` marks tables already merged on every device (e.g.
        after group_by(exchange='gather')) — no further collection needed."""
        tamper = self._chaos_point(
            "finalize", tamperable=self.packed and not replicated)
        if replicated:
            return _order_limit(t, sort_keys, limit)
        self._count("gather")
        if sort_keys or limit is not None:
            t = _order_limit(t, sort_keys, limit)   # local top-k first
        t, ov, cr, stats = ex.broadcast_table(t, self.axis, self.N,
                                              packed=self.packed, wire=wire,
                                              narrow=self.wire_narrow,
                                              tamper=tamper)
        self.overflow = self.overflow | ov
        self.corrupt = self.corrupt | cr
        self.stats.log.append(dataclasses.replace(stats, kind="gather"))
        return _order_limit(t, sort_keys, limit)


def _order_limit(t: Table, sort_keys, limit: int | None) -> Table:
    """Finalize's ORDER BY / LIMIT on a JAX table; the output is compact."""
    if limit is not None:
        return rel.sort_limit(t, sort_keys, limit) if sort_keys else \
            rel.limit(t, limit)
    return rel.sort_by(t, sort_keys) if sort_keys else rel.ensure_compact(t)


# ===========================================================================
# drivers
# ===========================================================================

def run_reference(query_fn, db: Database, wire_format: str | None = None,
                  ) -> tuple[dict, PlanStats]:
    ctx = RefContext(db, wire_format=wire_format)
    out = query_fn(ctx)
    if isinstance(out, dict) and out and \
            np.ndim(next(iter(out.values()))) == 0:
        out = {k: np.asarray([v]) for k, v in out.items()}
    return out, ctx.stats


def _np_db_to_tables(db: Database, pad: float = 1.0) -> dict[str, Table]:
    out = {}
    for name, t in db.tables.items():
        n = len(next(iter(t.values())))
        cap = max(8, int(math.ceil(n * pad / 8)) * 8)
        out[name] = from_numpy(t, capacity=cap)
    return out


def run_local(query_fn, db: Database, jit: bool = True,
              join_method: str = "sorted", use_kernel: bool | None = None,
              capacity_factor: float = 2.0, wire_format: str | None = None,
              chaos=None, return_overflow: bool = False,
              ) -> tuple[dict, PlanStats] | tuple[dict, PlanStats, bool]:
    tables = _np_db_to_tables(db)
    holder = {}

    def run(tables):
        ctx = LocalContext(db, tables, capacity_factor=capacity_factor,
                           join_method=join_method, use_kernel=use_kernel,
                           wire_format=wire_format)
        ctx.chaos = chaos
        out = query_fn(ctx)
        holder["stats"] = ctx.stats
        if isinstance(out, dict):
            out = Table({k: jnp.asarray(v).reshape(1) for k, v in out.items()},
                        jnp.asarray(1, jnp.int32))
        return rel.ensure_compact(out), ctx.overflow, ctx.corrupt

    fn = jax.jit(run) if jit else run
    out, overflow, corrupt = fn(tables)
    if bool(corrupt):
        raise wi.CorruptPayload("local run: payload integrity check failed")
    if return_overflow:
        # policy-loop callers (QueryRunner on a mesh-less topology) answer
        # overflow with capacity escalation instead of an assert
        return to_numpy(out), holder["stats"], bool(overflow)
    assert not bool(overflow), "capacity overflow in local run"
    return to_numpy(out), holder["stats"]


# -- host-side partitioning (paper §4.3) ------------------------------------

_C1 = np.uint64(0xFF51AFD7ED558CCD)
_C2 = np.uint64(0xC4CEB9FE1A85EC53)


def hash_partition_np(key: np.ndarray, n: int) -> np.ndarray:
    """splitmix64 finalizer — must match relational.hash_partition_ids."""
    with np.errstate(over="ignore"):
        k = key.astype(np.uint64)
        k = (k ^ (k >> np.uint64(33))) * _C1
        k = (k ^ (k >> np.uint64(33))) * _C2
        k = k ^ (k >> np.uint64(33))
        return (k % np.uint64(n)).astype(np.int32)


# Paper §4.3: lineitem by l_orderkey (co-partitioned with orders), partsupp by
# ps_partkey, others by primary key; nation/region replicated (tiny dims).
PARTITION_KEYS = {
    "lineitem": "l_orderkey",
    "orders": "o_orderkey",
    "partsupp": "ps_partkey",
    "part": "p_partkey",
    "supplier": "s_suppkey",
    "customer": "c_custkey",
    "nation": None,      # replicated
    "region": None,      # replicated
}


def shard_capacity(nrows: int, n: int) -> int:
    """Rows a hash partition of an ``nrows`` table over ``n`` shards has room
    for: the mean share plus eight standard deviations of it.  Rows come in
    groups of up to 7 per key (lineitem's lines of an order), which
    multiplies a binomial share's variance by at most 7 (8 here).  A shard
    that holds more anyway gets its own size (``partition_database``)."""
    mean = nrows / n
    return int(math.ceil(mean + 8.0 * math.sqrt(8.0 * mean)))


def partition_database(db: Database, n: int,
                       partition_keys: dict | None = None,
                       ) -> tuple[dict[str, dict], dict[str, int]]:
    """Host-side partitioning -> per-table (stacked shards dict, per-shard cap).

    Returns columns shaped (n*cap,) and counts shaped (n,) ready for shard_map
    with in_specs=P(axis).  Replicated tables (key None) appear whole in every
    shard — the standard treatment for tiny dimension tables.  Rows keep
    their table order within a shard.  A shard's capacity follows from the
    table's row count alone (``shard_capacity``), so tables of one size
    partition to the same shapes, and lower to the same programs, whatever
    their keys.
    """
    pk = dict(PARTITION_KEYS)
    if partition_keys:
        pk.update(partition_keys)
    out, caps = {}, {}
    for name, t in db.tables.items():
        nrows = len(next(iter(t.values())))
        key = pk.get(name)
        if key is None:
            counts = np.full(n, nrows)
            rows = [np.arange(nrows)] * n
        else:
            dest = hash_partition_np(np.asarray(t[key]), n)
            counts = np.bincount(dest, minlength=n)
            # a stable sort of 16-bit destinations is a radix sort
            order = np.argsort(dest.astype(np.int16), kind="stable")
            rows = np.split(order, np.cumsum(counts)[:-1])
        cap = nrows if key is None else shard_capacity(nrows, n)
        cap = max(8, int(math.ceil(max(cap, counts.max()) / 8)) * 8)
        cols = {}
        for cname, v in t.items():
            stacked = np.zeros((n * cap,), dtype=v.dtype)
            for d, r in enumerate(rows):
                np.take(v, r, out=stacked[d * cap: d * cap + len(r)])
            cols[cname] = stacked
        cols["__count"] = counts.astype(np.int32)
        out[name] = cols
        caps[name] = cap
    return out, caps


def place_partitions(sharded: dict[str, dict], mesh: Mesh,
                     axis: str = "data") -> dict[str, dict]:
    """Host partitions (``partition_database``) -> device arrays, each
    device's slice copied straight to that device along ``mesh[axis]``."""
    spec = NamedSharding(mesh, P(axis))
    return {name: {k: jax.device_put(v, spec) for k, v in cols.items()}
            for name, cols in sharded.items()}


def spmd_program(body: Callable, db: Database, mesh: Mesh,
                 axis: str = "data", capacity_factor: float = 2.0,
                 packed_exchange: bool = True, join_method: str = "sorted",
                 use_kernel: bool | None = None,
                 wire_format: str | None = None, chaos=None):
    """The one SPMD program builder: ``jax.jit(shard_map(...))`` of
    ``body(ctx, params)``, where every device runs ``body`` in a
    :class:`DistContext` over its partition of the ``place_partitions``
    tables and ``params`` (a pytree of scalars) enters replicated.

    The program is ``fn(tables, params) -> (result, overflow, corrupt)``,
    each with one entry per device along ``axis``; every device holds the
    whole (finalized) result, so ``first_shard`` reads it."""
    n = mesh.shape[axis]

    def spmd(tree, params):
        tables = {name: Table({k: v for k, v in cols.items()
                               if k != "__count"},
                              cols["__count"].reshape(()))
                  for name, cols in tree.items()}
        ctx = DistContext(db, tables, axis, n, capacity_factor,
                          packed_exchange, join_method, use_kernel,
                          wire_format)
        ctx.chaos = chaos
        out = body(ctx, params)
        if isinstance(out, dict):
            out = Table({k: jnp.asarray(v).reshape(1) for k, v in out.items()},
                        jnp.asarray(1, jnp.int32))
        out = rel.ensure_compact(out)   # host extraction slices [0, count)
        return (Table(dict(out.columns), out.count.reshape(1)),
                ctx.overflow.reshape(1), ctx.corrupt.reshape(1))

    return jax.jit(compat.shard_map(spmd, mesh=mesh, in_specs=(P(axis), P()),
                                    out_specs=P(axis)))


def first_shard(out: Table) -> Table:
    """The first device's copy of an ``spmd_program`` result: a Table of
    device arrays that ``to_numpy`` copies without another program."""
    def first(a):
        return a.addressable_shards[0].data
    return Table({k: first(v) for k, v in out.columns.items()},
                 np.asarray(first(out.count))[0])


def run_distributed(query_fn, db: Database, mesh: Mesh, axis: str = "data",
                    capacity_factor: float = 2.0, packed_exchange: bool = True,
                    partition_keys: dict | None = None,
                    join_method: str = "sorted",
                    use_kernel: bool | None = None,
                    wire_format: str | None = None,
                    chaos=None,
                    ) -> tuple[dict, PlanStats, Any]:
    """Run a query SPMD over ``mesh[axis]``; returns (result, stats, overflow).

    One logical process per device, all executing the same tensor program —
    the paper's MPI model realized as a single shard_map program.  A payload
    integrity failure (``ctx.corrupt``, set by the wire checksums — possibly
    via an armed ``chaos`` injector's tamper) raises :class:`CorruptPayload`
    host-side: corrupted buffers are never decoded into served results.
    """
    n = mesh.shape[axis]
    sharded, _ = partition_database(db, n, partition_keys)
    holder = {}

    def body(ctx, _params):
        out = query_fn(ctx)
        holder["stats"] = ctx.stats
        return out

    fn = spmd_program(body, db, mesh, axis, capacity_factor, packed_exchange,
                      join_method, use_kernel, wire_format, chaos)
    out, overflow, corrupt = fn(place_partitions(sharded, mesh, axis), {})
    if bool(np.any(np.asarray(corrupt))):
        raise wi.CorruptPayload(
            "distributed run: payload integrity check failed")
    return (to_numpy(first_shard(out)), holder["stats"],
            bool(np.any(np.asarray(overflow))))
