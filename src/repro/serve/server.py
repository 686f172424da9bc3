"""Multi-tenant serving: jit-once-per-template execution + batch sharing.

Two execution paths, one correctness story:

  * :class:`QueryServer` — the compiled path.  Each template is traced ONCE
    per (database, configuration): parameter bindings enter the jitted
    program as dtype-pinned traced scalars, so serving a new binding is a
    cache hit and a device call, never a re-trace.  ``recompiles`` counts
    actual traces (incremented INSIDE the traced body, so an accidental
    re-trace — dtype drift, structure drift — is counted and the bench gate
    ``benchmarks/bench_serve.py --check`` catches it).  Executables live in
    a :class:`repro.serve.cache.PlanCache`, so ``invalidate_stats`` /
    ``stats_override`` / table mutation evict them with the statistics they
    were derived from.  A served request whose domain-derived claims prove
    too tight for its binding surfaces as ``ctx.overflow``; the server
    re-runs it on a conservative entry (inference off, escalated capacity,
    its own cache key) — degraded latency, never a wrong answer.
  * :class:`BatchExecutor` — the eager batch path.  Admits N bound queries
    and extends the planner executor's per-plan DAG memo into a CROSS-QUERY
    memo keyed by (subtree content hash, relevant bindings): scans and
    common subplans — every query touching ``lineitem``, Q3/Q5 sharing a
    filtered-orders fragment — execute once per batch.  Execution is eager,
    so results are byte-identical to sequential one-query-at-a-time eager
    execution (pinned by ``tests/test_serve.py`` on both planner and both
    wire legs); an overflowing request forfeits its memo contributions and
    re-runs conservatively in isolation, so a lying bound can never poison a
    neighbour.

Topology: ``QueryServer(db, devices=n)`` with n > 1 serves on a mesh of
the first n chips JAX sees.  It hash-partitions the tables once (paper
§4.3, ``backend.PARTITION_KEYS``) and places the partitions on the mesh,
where they stay; each template compiles once into one SPMD program
(``backend.spmd_program``) whose exchanges are collectives between the
chips.  With n == 1 it compiles one-chip ``LocalContext`` programs over
whole tables.  The server carries a monotonically increasing
``topology_generation``.  Losing devices (:meth:`QueryServer.degrade`)
rebuilds the mesh over the survivors and re-places the partitions, and
bumps the generation — which is part of the executable cache key, so every
template re-traces exactly ONCE per (template, generation), never per
request — and the per-device footprint grows with the placed partitions.
With an :class:`AdmissionGate` configured,
:meth:`QueryServer.submit_guarded` returns structured outcomes instead of
opaque errors: :class:`Served` (full-width topology), :class:`Degraded`
(answered, but on a shrunken topology), or :class:`Shed` (declined or
queued because the estimated per-device footprint no longer fits the
degraded cluster; queued requests re-admit via :meth:`drain_backlog`).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as B
from repro.core import planner
from repro.core import relational as rel
from repro.core import tracing
from repro.core.compat import make_mesh
from repro.core.table import Table, to_numpy
from repro.core.wire import CorruptPayload
from .cache import PlanCache
from .templates import BoundQuery, PlanTemplate, TEMPLATES

__all__ = ["QueryServer", "BatchExecutor", "AdmissionGate",
           "Served", "Degraded", "Shed"]

_PDTYPE = {"int64": jnp.int64, "float64": jnp.float64}
_AXIS = "data"                 # the mesh axis the tables are partitioned on
# capacity escalation of the conservative rerun after an overflow
_RERUN_FACTOR = 4.0


def _any(flag) -> bool:
    """A program's overflow or corrupt flag: one per chip on a mesh."""
    return bool(np.any(np.asarray(flag)))


def _as_table(out):
    if isinstance(out, dict):        # ScalarResult: one-row table
        out = Table({k: jnp.asarray(v).reshape(1) for k, v in out.items()},
                    jnp.asarray(1, jnp.int32))
    return rel.ensure_compact(out)


@dataclasses.dataclass(frozen=True)
class AdmissionGate:
    """Per-device memory budget for admission control.

    ``hbm_bytes`` is the accelerator memory per device; a request is
    admitted while the server's estimated per-device footprint — database
    partition plus capacity-scaled working buffers — stays within
    ``headroom * hbm_bytes``.  After a topology shrink N -> N' the
    footprint grows by N/N', which is exactly what pushes oversized
    requests into :class:`Shed`."""
    hbm_bytes: float
    headroom: float = 0.8

    @property
    def budget_bytes(self) -> float:
        return self.headroom * self.hbm_bytes


@dataclasses.dataclass
class Served:
    """Request answered on the full-width (boot) topology."""
    name: str
    result: dict
    devices: int
    generation: int = 0


@dataclasses.dataclass
class Degraded:
    """Request answered correctly, but on a shrunken topology — the caller
    sees degraded capacity/latency, never a degraded answer."""
    name: str
    result: dict
    devices: int
    generation: int
    lost: int = 0                 # devices below boot width


@dataclasses.dataclass
class Shed:
    """Request NOT executed: its estimated footprint does not fit the
    current (degraded) cluster.  ``queued`` means it sits in the server
    backlog and re-admits via :meth:`QueryServer.drain_backlog` once
    capacity returns."""
    name: str
    reason: str
    estimated_bytes: float
    budget_bytes: float
    devices: int
    generation: int
    queued: bool = False


class QueryServer:
    """Serve parameterized queries from jit-compiled template executables."""

    def __init__(self, db, capacity_factor: float = 2.0,
                 join_method: str = "sorted", use_kernel: bool | None = None,
                 wire_format: str | None = None,
                 cache: PlanCache | None = None,
                 devices: int = 1, gate: AdmissionGate | None = None):
        self.db = db
        self.capacity_factor = capacity_factor
        self.join_method = join_method
        self.use_kernel = use_kernel
        self.wire_format = wire_format
        self.cache = cache if cache is not None else PlanCache()
        self.recompiles = 0          # jit traces (counted inside the trace)
        self.cache_hits = 0
        self.overflow_reruns = 0
        self.approx_served = 0       # answers served off a sample rung
        self.approx_escalations = 0  # tolerance misses climbed past
        self.approx_refused = 0      # non-estimable shapes served exact
        self._requests = 0           # submit's sequence number
        # join indexes each exact program builds, by method (trace time)
        self._index_builds: dict[tuple, dict[str, int]] = {}
        # group-by segment reductions each exact program makes, by segsum
        # path (trace time)
        self._agg_reductions: dict[tuple, dict[str, int]] = {}
        # exchanges each mesh program makes, by kind, and bytes (trace time)
        self._exchanges: dict[tuple, dict[str, int]] = {}
        self._phases = tracing.Phases()
        if devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        self.boot_devices = int(devices)
        self.devices = int(devices)
        self.topology_generation = 0
        self.gate = gate
        self.shed_count = 0
        self.backlog: list[tuple[PlanTemplate, dict | None, bool | None]] = []
        self._mesh = None              # None: one chip, whole tables
        if devices == 1:
            self._tables = B._np_db_to_tables(db)
        else:
            chips = jax.devices()
            if len(chips) < devices:
                raise ValueError(f"devices={devices}, but JAX sees "
                                 f"{len(chips)}")
            self._chips = chips[:devices]
            self._place()

    # -- topology -----------------------------------------------------------
    def _place(self) -> None:
        """Partition the tables over the live chips and put them there."""
        with self._phases.span(tracing.PLACE, devices=self.devices):
            self._mesh = make_mesh((self.devices,), (_AXIS,),
                                   devices=self._chips[:self.devices])
            self._tables = None          # free the old placement first
            sharded, _ = B.partition_database(self.db, self.devices)
            self._tables = B.place_partitions(sharded, self._mesh, _AXIS)

    def _resize(self, devices: int) -> int:
        if devices != self.devices:
            self.devices = int(devices)
            self.topology_generation += 1
            if self._mesh is not None:
                self._place()
        return self.topology_generation

    def degrade(self, devices: int) -> int:
        """Shrink the topology to its first ``devices`` chips: on a mesh,
        the partitions are re-placed over the survivors.  Bumps the topology
        generation — every template re-traces exactly once against the new
        generation (the generation is in the executable cache key).
        Returns the new generation."""
        if not 1 <= devices <= self.devices:
            raise ValueError(
                f"degrade to {devices} from {self.devices} devices")
        return self._resize(devices)

    def restore(self, devices: int | None = None) -> int:
        """Recovered capacity (default: back to boot width).  A new
        generation as well — the topology changed."""
        devices = self.boot_devices if devices is None else int(devices)
        if not 1 <= devices <= self.boot_devices:
            raise ValueError(f"restore to {devices} devices")
        return self._resize(devices)

    def footprint_bytes(self, factor: float | None = None) -> float:
        """Estimated footprint of the fullest chip: the bytes of the tables
        placed on it plus exchange/join working buffers, which the engine
        sizes as ``capacity_factor`` x those bytes."""
        factor = self.capacity_factor if factor is None else factor
        per_chip: dict = {}
        for a in jax.tree.leaves(self._tables):
            for shard in a.addressable_shards:
                per_chip[shard.device] = (per_chip.get(shard.device, 0)
                                          + shard.data.nbytes)
        return max(per_chip.values()) * (1.0 + float(factor))

    def _exe_key(self, template: PlanTemplate, infer: bool,
                 factor: float) -> tuple:
        return ("exe", template.signature(), bool(infer), self.wire_format,
                float(factor), self.join_method, self.use_kernel,
                self.topology_generation)

    def _executable(self, template: PlanTemplate, infer: bool, factor: float):
        key = self._exe_key(template, infer, factor)
        fn = self.cache.get(self.db, key)
        if fn is None:
            fn = self._compile(template, infer, factor, key)
            self.cache.put(self.db, key, fn)
        else:
            self.cache_hits += 1
        return fn

    def index_builds(self, template: PlanTemplate | int,
                     infer: bool | None = None,
                     rerun: bool = False) -> dict[str, int] | None:
        """Join indexes the template's exact program builds, by method
        (``{"direct": 3, "sorted": 1}``), as its last trace counted them;
        ``rerun=True`` reads the conservative rerun's program.  None where
        that program has not been traced."""
        return self._index_builds.get(self._key_of(template, infer, rerun))

    def agg_reductions(self, template: PlanTemplate | int,
                       infer: bool | None = None,
                       rerun: bool = False) -> dict[str, int] | None:
        """Group-by segment reductions the template's exact program makes,
        by ``segsum`` path (``{"mxu": 1, "masked": 7}``; ``mxu`` / ``masked``
        / ``scatter``), as its last trace counted them; sort-path group-bys
        count none.  ``rerun=True`` reads the conservative rerun's program.
        None where that program has not been traced."""
        return self._agg_reductions.get(self._key_of(template, infer, rerun))

    def _key_of(self, template: PlanTemplate | int, infer: bool | None,
                rerun: bool) -> tuple:
        if isinstance(template, int):
            template = TEMPLATES[template]
        if infer is None:
            infer = planner.planner_default()
        return self._exe_key(template, infer and not rerun,
                             self.capacity_factor *
                             (_RERUN_FACTOR if rerun else 1.0))

    def exchanges(self, template: PlanTemplate | int,
                  infer: bool | None = None,
                  rerun: bool = False) -> dict[str, int] | None:
        """Exchanges between chips that the template's mesh program makes,
        by kind (``shuffle``, ``broadcast``, ``gather``, ``allreduce``), and
        the ``bytes`` they ship from each chip, as its last trace counted
        them.  None where that program has not been traced, and on one chip,
        where nothing is exchanged."""
        return self._exchanges.get(self._key_of(template, infer, rerun))

    def _compile(self, template: PlanTemplate, infer: bool, factor: float,
                 key: tuple):
        query = template.query
        # host-side, once per (template, db): domain-sound hints/wire bounds
        info = query.info(self.db) if infer else None
        if self._mesh is not None:
            return self._compile_spmd(query, info, factor, key)

        def run(tables, pvals):
            # trace-time side effect: every (re)trace of this executable is
            # a counted recompile — the bench gate's ground truth
            self.recompiles += 1
            ctx = B.LocalContext(self.db, tables, capacity_factor=factor,
                                 join_method=self.join_method,
                                 use_kernel=self.use_kernel,
                                 wire_format=self.wire_format)
            out = planner._Executor(ctx, info, params=pvals).run(query.plan)
            self._index_builds[key] = dict(ctx.stats.index_builds)
            self._agg_reductions[key] = dict(ctx.stats.agg_reductions)
            return _as_table(out), ctx.overflow, ctx.corrupt

        return jax.jit(run)

    def _compile_spmd(self, query, info, factor: float, key: tuple):
        """One SPMD program over the placed partitions: the parameters enter
        replicated, as traced scalars."""
        def run(ctx, pvals):
            self.recompiles += 1
            out = planner._Executor(ctx, info, params=pvals).run(query.plan)
            self._index_builds[key] = dict(ctx.stats.index_builds)
            self._agg_reductions[key] = dict(ctx.stats.agg_reductions)
            st = ctx.stats
            self._exchanges[key] = {
                "shuffle": st.shuffles, "broadcast": st.broadcasts,
                "gather": st.final_gathers, "allreduce": st.allreduces,
                "bytes": sum(e.total_bytes for e in st.log)}
            return out

        return B.spmd_program(run, self.db, self._mesh, _AXIS,
                              capacity_factor=factor,
                              join_method=self.join_method,
                              use_kernel=self.use_kernel,
                              wire_format=self.wire_format)

    def compiled(self, template: PlanTemplate | int,
                 infer: bool | None = None) -> jax.stages.Compiled:
        """The executable ``submit`` runs for ``template`` (exact serving),
        as compiled for this server's chips; compiles it if not yet served.
        """
        if isinstance(template, int):
            template = TEMPLATES[template]
        if infer is None:
            infer = planner.planner_default()
        pvals = {name: jnp.asarray(v, _PDTYPE[template.params[name].dtype])
                 for name, v in template.bind().values.items()}
        fn = self._executable(template, infer, self.capacity_factor)
        return fn.lower(self._tables, pvals).compile()

    def phase_stats(self) -> dict[str, dict[str, float]]:
        """Host seconds of each ``serve.*`` span of ``submit`` since the
        server was built: ``{name: {"count", "total_s", "max_s"}}``."""
        return self._phases.stats()

    def submit(self, template: PlanTemplate | int,
               bindings: dict[str, Any] | None = None,
               infer: bool | None = None,
               tolerance: float | None = None,
               confidence: float = 0.95) -> dict:
        """Execute one parameterized request; returns the numpy result.

        ``tolerance=`` opts into approximate serving: the answer comes from
        the smallest sample rung whose relative CI half-width (at
        ``confidence``) fits the tolerance, escalating up the ladder
        otherwise — each rung a separately-cached executable (the rung is in
        the cache key, so approximate and exact artifacts never collide).
        Plans the rewrite pass refuses run exact.  Default tolerance comes
        from ``REPRO_APPROX`` (unset = exact serving).
        """
        if isinstance(template, int):
            template = TEMPLATES[template]
        self._requests += 1
        with self._phases.span(tracing.SUBMIT, request=self._requests,
                               template=template.name):
            return self._submit(template, bindings, infer, tolerance,
                                confidence)

    def _submit(self, template: PlanTemplate, bindings, infer, tolerance,
                confidence) -> dict:
        span = self._phases.span
        if infer is None:
            infer = planner.planner_default()
        with span(tracing.BIND):
            bound = template.bind(**(bindings or {}))
            # dtype-pinned traced scalars; every declared parameter is
            # always present, so the pytree structure (and hence the
            # trace) is stable
            pvals = {name: jnp.asarray(v,
                                       _PDTYPE[template.params[name].dtype])
                     for name, v in bound.values.items()}
        if tolerance is None:
            from repro.approx.progressive import approx_default
            tolerance = approx_default()
        if tolerance is not None:
            res = self._submit_approx(template, pvals, infer,
                                      float(tolerance), confidence)
            if res is not None:
                return res
            self.approx_refused += 1
        with span(tracing.LOOKUP):
            fn = self._executable(template, infer, self.capacity_factor)
        with span(tracing.DISPATCH):
            out, overflow, corrupt = fn(self._tables, pvals)
        with span(tracing.WAIT):
            overflowed = _any(overflow)
        if overflowed:
            # a domain-derived claim was too tight for this binding (or the
            # statistics lied): re-run conservatively — no hints, escalated
            # capacity, full-width wire — under its own cache key so healthy
            # traffic keeps the fast entry
            with span(tracing.RERUN):
                self.overflow_reruns += 1
                fn = self._executable(template, False,
                                      self.capacity_factor * _RERUN_FACTOR)
                out, overflow, corrupt = fn(self._tables, pvals)
                overflowed = _any(overflow)
        if _any(corrupt):
            raise CorruptPayload("serve: payload integrity check failed")
        if overflowed:
            raise RuntimeError(
                f"{template.name}: overflow persists on the conservative "
                f"rerun (capacity_factor="
                f"{self.capacity_factor * _RERUN_FACTOR})")
        with span(tracing.FETCH):
            # on a mesh every chip holds the whole answer: copy the first's
            return to_numpy(out if self._mesh is None else B.first_shard(out))

    # -- approximate serving (repro.approx) --------------------------------
    def _approx_rewrite(self, template: PlanTemplate, den: int):
        """Rung rewrite of a template, cached (and invalidated) with the
        statistics it was derived from."""
        from repro.approx import rewrite as AR
        from repro.approx import sampling as AS
        key = ("approx-rw", template.signature(), int(den), AS.DEFAULT_SEED)
        got = self.cache.get(self.db, key)
        if got is None:
            rw = AR.rewrite_for_rung(template.query, self.db, den)
            self.cache.put(self.db, key, ("rw", rw))
        else:
            self.cache_hits += 1
            rw = got[1]
        return rw

    def _approx_executable(self, template: PlanTemplate, rw, infer: bool,
                           factor: float):
        from repro.approx import sampling as AS
        tkey = ("approx-tables", rw.table, rw.strata, int(rw.den),
                AS.DEFAULT_SEED)
        tables = self.cache.get(self.db, tkey)
        if tables is None:
            tables = B._np_db_to_tables(rw.db)
            self.cache.put(self.db, tkey, tables)
        # the rung is part of the key: approximate and exact executables
        # (and different rungs) never collide in the cache
        key = ("exe-approx", template.signature(), int(rw.den), bool(infer),
               self.wire_format, float(factor), self.join_method,
               self.use_kernel, self.topology_generation)
        fn = self.cache.get(self.db, key)
        if fn is None:
            query, rdb = rw.query, rw.db
            info = query.info(rdb) if infer else None

            def run(tables, pvals):
                self.recompiles += 1
                ctx = B.LocalContext(rdb, tables, capacity_factor=factor,
                                     join_method=self.join_method,
                                     use_kernel=self.use_kernel,
                                     wire_format=self.wire_format)
                out = planner._Executor(ctx, info, params=pvals).run(
                    query.plan)
                return _as_table(out), ctx.overflow, ctx.corrupt

            fn = jax.jit(run)
            self.cache.put(self.db, key, fn)
        else:
            self.cache_hits += 1
        return fn, tables

    def _submit_approx(self, template: PlanTemplate, pvals: dict,
                       infer: bool, tolerance: float,
                       confidence: float) -> dict | None:
        """Climb the sample ladder; None means the shape refused (go exact)."""
        from repro.approx import sampling as AS
        for den in AS.LADDER:
            rw = self._approx_rewrite(template, den)
            if rw is None:
                return None
            fn, tables = self._approx_executable(
                template, rw, infer, self.capacity_factor)
            out, overflow, corrupt = fn(tables, pvals)
            if bool(overflow):
                self.overflow_reruns += 1
                fn, tables = self._approx_executable(
                    template, rw, False, self.capacity_factor * _RERUN_FACTOR)
                out, overflow, corrupt = fn(tables, pvals)
            if bool(corrupt):
                raise CorruptPayload(
                    "serve: payload integrity check failed")
            if bool(overflow):
                raise RuntimeError(
                    f"{template.name}~r{den}: overflow persists on the "
                    f"conservative rerun")
            est = rw.finalize(to_numpy(out), confidence)
            if est.rel_width <= tolerance or den == 1:
                self.approx_served += 1
                return est.result
            self.approx_escalations += 1
        return None    # unreachable: the den == 1 rung always answers

    def serve(self, requests, infer: bool | None = None,
              tolerance: float | None = None) -> list[dict]:
        """Submit a stream of ``(template_or_qid, bindings)`` requests."""
        return [self.submit(t, b, infer=infer, tolerance=tolerance)
                for t, b in requests]

    # -- capacity-aware admission ------------------------------------------
    def submit_guarded(self, template: PlanTemplate | int,
                       bindings: dict[str, Any] | None = None,
                       infer: bool | None = None,
                       queue: bool = True) -> Served | Degraded | Shed:
        """Admission-gated submit with structured outcomes.

        With no :class:`AdmissionGate` configured every request is admitted.
        Otherwise a request whose estimated per-device footprint exceeds the
        gate's budget at the LIVE width is not executed: it is queued on the
        server backlog (``queue=True``, the default) or declined outright —
        both surfaced as :class:`Shed`, never as an opaque error.  Admitted
        requests on a shrunken topology come back :class:`Degraded`."""
        if isinstance(template, int):
            template = TEMPLATES[template]
        if self.gate is not None:
            est = self.footprint_bytes()
            if est > self.gate.budget_bytes:
                self.shed_count += 1
                if queue:
                    self.backlog.append((template, bindings, infer))
                return Shed(
                    name=template.name, queued=queue,
                    reason=(f"estimated per-device footprint "
                            f"{est / 1e6:.1f} MB exceeds budget "
                            f"{self.gate.budget_bytes / 1e6:.1f} MB at "
                            f"{self.devices} devices"),
                    estimated_bytes=est,
                    budget_bytes=self.gate.budget_bytes,
                    devices=self.devices,
                    generation=self.topology_generation)
        result = self.submit(template, bindings, infer=infer)
        if self.devices < self.boot_devices:
            return Degraded(name=template.name, result=result,
                            devices=self.devices,
                            generation=self.topology_generation,
                            lost=self.boot_devices - self.devices)
        return Served(name=template.name, result=result,
                      devices=self.devices,
                      generation=self.topology_generation)

    def drain_backlog(self) -> list[Served | Degraded | Shed]:
        """Re-admit queued requests (after :meth:`restore` or a capacity
        change).  Requests that still do not fit go back on the backlog."""
        pending, self.backlog = self.backlog, []
        return [self.submit_guarded(t, b, infer=i, queue=True)
                for t, b, i in pending]


class _SharedMemoExecutor(planner._Executor):
    """Planner executor whose node memo extends across queries.

    Key = (subtree content hash, the bindings of the parameters that subtree
    can observe, inference leg).  Content-addressing makes distinct plan
    objects with identical logical subtrees share; restricting the key to
    the REACHABLE parameters lets two bindings share every subtree that
    doesn't depend on where they differ (all scans, for one).  Sound because
    per-subtree planner decisions (hints, wire bounds) depend only on the
    subtree's content and the database statistics — identical key, identical
    table."""

    def __init__(self, ctx, info, params, subsigs, shared, added, owner):
        super().__init__(ctx, info, params=params)
        self._subsigs = subsigs
        self._shared = shared
        self._added = added
        self._owner = owner

    def _exec(self, node):
        got = self.memo.get(id(node))
        if got is not None:
            return got
        sig, pnames = self._subsigs[id(node)]
        key = (sig, tuple(sorted((p, self.params.get(p)) for p in pnames)),
               self.info is not None)
        out = self._shared.get(key)
        if out is not None:
            self._owner.shared_hits += 1
            self.memo[id(node)] = out
            return out
        out = super()._exec(node)    # recursion re-enters this override
        self._shared[key] = out
        self._added.append(key)
        return out


class BatchExecutor:
    """Execute a batch of bound queries eagerly with cross-query sharing."""

    def __init__(self, db, capacity_factor: float = 2.0,
                 join_method: str = "sorted", use_kernel: bool | None = None,
                 wire_format: str | None = None):
        self.db = db
        self.capacity_factor = capacity_factor
        self.join_method = join_method
        self.use_kernel = use_kernel
        self.wire_format = wire_format
        self.shared_hits = 0         # cross-query memo hits
        self.overflow_reruns = 0
        self._tables = B._np_db_to_tables(db)

    def _ctx(self, factor: float):
        return B.LocalContext(self.db, self._tables, capacity_factor=factor,
                              join_method=self.join_method,
                              use_kernel=self.use_kernel,
                              wire_format=self.wire_format)

    def run_batch(self, requests, infer: bool | None = None) -> list[dict]:
        """``requests``: (template, bindings) pairs (or BoundQuery directly).

        Returns per-request numpy results, byte-identical to running each
        request alone (eager) in submission order.
        """
        if infer is None:
            infer = planner.planner_default()
        shared: dict = {}
        ctx = self._ctx(self.capacity_factor)
        results: list[dict] = []
        for req in requests:
            bound = req if isinstance(req, BoundQuery) else \
                req[0].bind(**(req[1] or {}))
            template = bound.template
            info = template.query.info(self.db) if infer else None
            added: list = []
            ex = _SharedMemoExecutor(ctx, info, bound.values,
                                     template.subplan_signatures(), shared,
                                     added, self)
            out = _as_table(ex.run(template.query.plan))
            if bool(ctx.corrupt):
                raise CorruptPayload(
                    "batch: payload integrity check failed")
            if bool(ctx.overflow):
                # this request's claims lied: its memo contributions are not
                # trustworthy state — forfeit them, re-run the request alone
                # conservatively, and start the NEXT request on a fresh
                # context (the overflow flag is sticky by design)
                for k in added:
                    shared.pop(k, None)
                results.append(self._conservative(bound))
                ctx = self._ctx(self.capacity_factor)
                continue
            results.append(to_numpy(out))
        return results

    def _conservative(self, bound: BoundQuery) -> dict:
        self.overflow_reruns += 1
        ctx = self._ctx(self.capacity_factor * _RERUN_FACTOR)
        out = _as_table(bound.with_inference(False)(ctx))
        if bool(ctx.corrupt):
            raise CorruptPayload("batch: payload integrity check failed")
        if bool(ctx.overflow):
            raise RuntimeError(
                f"{bound.template.name}: overflow persists on the "
                f"conservative rerun")
        return to_numpy(out)
