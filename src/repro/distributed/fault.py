"""Fault tolerance + skew mitigation for distributed queries and training.

Queries: the paper's model (§2.4) — re-execution at interactive speed —
extended with a failure TAXONOMY (:class:`repro.distributed.chaos.FailureKind`)
so the runner reacts to what actually went wrong instead of retrying blindly:

  TRANSIENT      environment fault (node loss, flaky link, timeout): retry
                 with bounded exponential backoff (:class:`RetryPolicy`).
  OVERFLOW       structured capacity failure (a shuffle bucket, a shrink, a
                 hash-join bucket table, a narrowed wire lane, or the hash-
                 aggregation dictionary exceeded its planned size — all raise
                 ``ctx.overflow``, never assert locally): escalate the
                 capacity factor; after a second overflow, recompile with
                 inference dropped (no hints -> no hint-induced overflow).
                 The factor also scales the hash-join per-bucket capacity
                 (``_BaseContext.bucket_cap``) AND the group-by dictionary
                 (``relational.group_aggregate(method="hash")`` sizes it
                 ``groups_hint * factor``), so escalation genuinely enlarges
                 both.
  CORRUPT        a packed payload failed its wire integrity checksum
                 (:class:`repro.core.wire.CorruptPayload`): re-run on the
                 conservative wide format — never serve the bad buffer.
  DETERMINISTIC  a plan-author bug (TypeError, ValueError, assertion …), or
                 a program the device refuses (a kernel the compiler
                 rejects, a program that exhausts device memory): raised
                 immediately on attempt 1 — re-execution cannot fix code.
  DEVICE_LOST    one or more mesh participants are permanently dead
                 (:class:`repro.distributed.chaos.DeviceLost`): retrying on
                 the same topology can only fail again.  The runner shrinks
                 the mesh to the survivors (:func:`surviving_mesh`), bumps
                 its topology generation, re-derives the perf-model budgets
                 at the new width (``ClusterSpec.with_devices`` — Hockney /
                 Eq. 3 pricing uses N', not the boot-time N), re-plans and
                 re-executes.  ``run_distributed`` re-partitions the
                 database over the surviving N' devices, so per-device
                 capacity grows by N/N' automatically; with a lineage store
                 armed, snapshots written at width N are re-sharded onto N'
                 instead of discarded.

Each attempt is logged in a :class:`RunReport` (failure kind, chaos cut
point, backoff, snapshot reuse, live device count, topology generation)
surfaced through ``launch/report.py``; the seeded chaos harness
(:mod:`repro.distributed.chaos`, ``REPRO_CHAOS`` env) drives every branch
of this policy deterministically in CI.

Skew: the monitor computes the paper's §3.5 statistic (per-node send/recv max
over mean) from exchange recv-counts; the planner consults Eq. 3 to pick
broadcast vs shuffle given table sizes, and hot-key salting splits dominant
keys before a grouped shuffle (local pre-aggregation already bounds
per-key payload — salting bounds residual placement skew).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core import backend as B
from repro.core import perfmodel as pm
from repro.core.wire import CorruptPayload
from .chaos import (ChaosInjector, DeviceLost, FailureKind, FiredFault,
                    TransientFault, _mix, resolve_lost)

__all__ = [
    "QueryRunner", "RunResult", "RunReport", "AttemptReport", "RetryPolicy",
    "FailureKind", "QueryTimeout", "classify_failure", "surviving_mesh",
    "choose_exchange", "skew_imbalance", "salt_hot_keys",
]


# exception types that indicate a bug in plan/query code, not the
# environment: re-executing is useless and masks the error — raise on
# attempt 1 (the old catch-all burned max_attempts re-runs on these)
_DETERMINISTIC_EXC = (TypeError, ValueError, KeyError, IndexError,
                      AttributeError, AssertionError, NameError,
                      ZeroDivisionError)
# JAX runtime errors that re-running the same program cannot fix: a Pallas
# kernel the Mosaic compiler refuses, a program that does not fit device
# memory.  Other statuses (INTERNAL included) may be device or runtime
# faults, and stay TRANSIENT.
_DETERMINISTIC_XLA = ("Mosaic failed to compile", "RESOURCE_EXHAUSTED:")


def classify_failure(exc: BaseException) -> FailureKind:
    """Map a raised exception onto the failure taxonomy.

    ``CorruptPayload`` -> CORRUPT; plan-author bug types and JAX runtime
    errors whose status says the program itself is at fault (compile
    failure, out of device memory) -> DETERMINISTIC; everything else
    (``TransientFault``, OSError, timeouts, the unknown) is treated as a
    TRANSIENT environment fault and retried — the conservative default,
    bounded by ``RetryPolicy.max_attempts``.
    """
    if isinstance(exc, DeviceLost):
        return FailureKind.DEVICE_LOST
    if isinstance(exc, CorruptPayload):
        return FailureKind.CORRUPT
    if isinstance(exc, _DETERMINISTIC_EXC):
        return FailureKind.DETERMINISTIC
    if isinstance(exc, jax.errors.JaxRuntimeError) and \
            any(m in str(exc) for m in _DETERMINISTIC_XLA):
        return FailureKind.DETERMINISTIC
    return FailureKind.TRANSIENT


class QueryTimeout(RuntimeError):
    """The runner's OVERALL wall-clock deadline (``QueryRunner.deadline_s``)
    expired with attempts still in the budget.  Distinct from the
    per-attempt straggler deadline (``RetryPolicy.deadline_s``), which
    discards one late attempt; this one ends the query.  Carries the
    partial :class:`RunReport` so the caller can audit what was tried."""

    def __init__(self, message: str, report: "RunReport"):
        super().__init__(message)
        self.report = report


def surviving_mesh(mesh: Mesh, lost: tuple[int, ...], axis: str) -> Mesh:
    """A fresh 1-D mesh over ``axis`` holding every device of ``mesh``
    except the ``lost`` ranks (ranks index the mesh's flat device order).
    The surviving devices are kept explicitly — never re-enumerated from
    the backend, which would resurrect the dead ones."""
    devices = [d for i, d in enumerate(np.asarray(mesh.devices).flat)
               if i not in set(lost)]
    if not devices:
        raise ValueError(f"no survivors: lost {lost!r} of mesh {mesh.shape}")
    return Mesh(np.asarray(devices), (axis,))


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded retries with exponential backoff and an optional per-attempt
    deadline.

    ``deadline_s``: an attempt whose wall time exceeds it is treated as a
    straggler — its (correct) result is discarded and the query re-executes,
    the speculative-retry semantics of §2.4 (never applied to the final
    attempt: a late answer beats none).

    ``jitter``: with it on, :meth:`backoff` applies seeded decorrelated
    jitter — pure exponential backoff synchronizes the retry storms of
    concurrent runners that failed together.  The jitter is derived from a
    seed (the runner passes the chaos seed, or ``seed`` here), so chaos
    runs stay bit-deterministic; it is bounded to
    ``[backoff_s, max_backoff_s]``.
    """
    max_attempts: int = 4
    backoff_s: float = 0.05       # first TRANSIENT retry waits this long
    backoff_mult: float = 2.0     # then doubles ...
    max_backoff_s: float = 2.0    # ... up to this cap
    deadline_s: float | None = None
    jitter: bool = False          # seeded decorrelated jitter on backoff
    seed: int | None = None       # jitter seed override (else: chaos seed)

    def backoff(self, transient_failures: int,
                seed: int | None = None) -> float:
        """Sleep before the next attempt after the n-th transient failure.

        Without ``jitter`` (or with no seed available): bounded exponential,
        exactly ``backoff_s * mult^(n-1)`` capped at ``max_backoff_s``.
        With it: decorrelated jitter — uniform (seeded, deterministic) in
        ``[backoff_s, min(max_backoff_s, 3 * previous_sleep)]`` — each
        runner's sequence de-synchronizes from its neighbours' while keeping
        the same bounds."""
        exp = min(self.backoff_s * self.backoff_mult
                  ** (transient_failures - 1), self.max_backoff_s)
        seed = self.seed if self.seed is not None else seed
        if not self.jitter or seed is None:
            return exp
        prev = self.backoff(transient_failures - 1, seed) \
            if transient_failures > 1 else self.backoff_s
        hi = min(self.max_backoff_s, max(self.backoff_s, 3.0 * prev))
        u = (_mix(seed, "backoff", transient_failures) % 65536) / 65535.0
        return self.backoff_s + u * (hi - self.backoff_s)


@dataclasses.dataclass
class AttemptReport:
    """One row of the per-attempt audit trail."""
    attempt: int
    outcome: str                  # "ok" | FailureKind value
    wall_s: float
    capacity_factor: float
    wire_format: str | None
    inference: bool
    backoff_s: float = 0.0        # slept AFTER this attempt
    cut: str | None = None        # chaos cut point, when injected
    snapshots_reused: int = 0     # lineage: exchange snapshots resumed from
    error: str = ""
    devices: int = 0              # live mesh width this attempt ran on
    generation: int = 0           # topology generation (0 = boot mesh)
    rung: int = 0                 # approx ladder denominator (0 = exact plan)
    ci_width: float | None = None  # rel. CI half-width of an approx answer


@dataclasses.dataclass
class RunReport:
    """Full audit of one ``QueryRunner.run``: every attempt + every fault the
    chaos harness injected.  Rendered by ``launch/report.py --section runs``."""
    attempts: list[AttemptReport] = dataclasses.field(default_factory=list)
    injected: list[FiredFault] = dataclasses.field(default_factory=list)

    def outcomes(self) -> list[str]:
        return [a.outcome for a in self.attempts]

    def rows(self) -> list[dict]:
        return [dataclasses.asdict(a) for a in self.attempts]


@dataclasses.dataclass
class RunResult:
    result: dict
    stats: B.PlanStats
    attempts: int
    capacity_factor: float
    wall_s: float
    report: RunReport = dataclasses.field(default_factory=RunReport)


class QueryRunner:
    """Policy-driven re-execution (paper §2.4 fault tolerance + taxonomy).

    ``chaos``: a :class:`ChaosInjector` armed for every attempt (defaults to
    the ``REPRO_CHAOS`` env leg — unset means no injection).  ``lineage``: a
    :class:`repro.distributed.lineage.LineageStore`; when given, attempts
    execute eagerly on the single-device engine persisting every exchange
    boundary, so a mid-query failure resumes from the last durable exchange
    instead of re-executing the whole plan (the distributed engine keeps the
    paper's whole-query re-execution — snapshots cannot be written from
    inside a compiled SPMD program).
    """

    def __init__(self, db, mesh, axis: str = "data",
                 capacity_factor: float = 2.0, max_attempts: int = 4,
                 escalation: float = 2.0, packed_exchange: bool = True,
                 join_method: str = "sorted", wire_format: str | None = None,
                 policy: RetryPolicy | None = None,
                 chaos: ChaosInjector | None = None,
                 lineage=None, deadline_s: float | None = None,
                 cluster: pm.ClusterSpec | None = None,
                 local_jit: bool = True):
        self.db = db
        self.mesh = mesh
        self.axis = axis
        self.capacity_factor = capacity_factor
        self.escalation = escalation
        self.packed = packed_exchange
        self.join_method = join_method
        self.wire_format = wire_format
        self.policy = policy or RetryPolicy(max_attempts=max_attempts)
        self.chaos = chaos if chaos is not None else ChaosInjector.from_env()
        self.lineage = lineage
        self.deadline_s = deadline_s          # overall wall-clock budget
        self.cluster = cluster                # perf-model spec, kept at N'
        self.boot_devices = int(mesh.shape[axis]) if mesh is not None else 1
        self.topology_generation = 0
        self.lost_devices: tuple[int, ...] = ()
        self.local_jit = local_jit    # mesh-less single-device attempts

    # retained for callers that introspect the runner
    @property
    def max_attempts(self) -> int:
        return self.policy.max_attempts

    @property
    def devices(self) -> int:
        """Live mesh width (N' after topology shrinks, N at boot)."""
        if self.mesh is None:          # lineage-only eager path
            return 1
        return int(self.mesh.shape[self.axis])

    def _jitter_seed(self) -> int | None:
        if self.policy.seed is not None:
            return self.policy.seed
        return self.chaos.plan.seed if self.chaos is not None else None

    def _shrink_topology(self, exc: DeviceLost) -> tuple[int, ...]:
        """The topology-elastic rung: drop the dead ranks, re-derive the
        mesh over the survivors, bump the generation, and re-scale the
        perf-model budgets to the new width.  Returns the resolved dead
        ranks (empty when nothing can shrink — a 1-device mesh)."""
        world = self.devices
        lost = resolve_lost(exc, world)
        if not lost:
            return ()
        self.mesh = surviving_mesh(self.mesh, lost, self.axis)
        self.topology_generation += 1
        self.lost_devices = self.lost_devices + lost
        if self.cluster is not None:
            # Hockney / Eq. 3 pricing must see N', not the boot-time N
            self.cluster = self.cluster.with_devices(self.devices)
        return lost

    def _attempt(self, fn, factor: float, wire_format: str | None):
        """Execute one attempt; returns (result, stats, overflow, reused)."""
        if self.lineage is not None:
            from . import lineage as ln
            return ln.run_resumable(
                fn, self.db, self.lineage, capacity_factor=factor,
                join_method=self.join_method, wire_format=wire_format,
                chaos=self.chaos, n_devices=self.devices)
        if self.mesh is None:
            # mesh-less runner (the progressive approx ladder's default):
            # single-device execution under the SAME policy loop — overflow
            # is returned, not asserted, so capacity escalation still works
            result, stats, overflow = B.run_local(
                fn, self.db, jit=self.local_jit, capacity_factor=factor,
                join_method=self.join_method, wire_format=wire_format,
                chaos=self.chaos, return_overflow=True)
            return result, stats, overflow, 0
        result, stats, overflow = B.run_distributed(
            fn, self.db, self.mesh, self.axis, capacity_factor=factor,
            packed_exchange=self.packed, join_method=self.join_method,
            wire_format=wire_format, chaos=self.chaos)
        return result, stats, overflow, 0

    def run(self, query_fn, bindings: dict | None = None) -> RunResult:
        """Execute ``query_fn`` under the retry policy.

        ``query_fn`` may be a plain ``fn(ctx)``, a compiled query, or a
        parameterized plan template (``repro.serve.PlanTemplate``); in the
        template case pass the parameter values as ``bindings`` — they are
        bound ONCE here (domain-validated at bind time) and every retry,
        capacity escalation and hint-drop recompilation reuses the same
        bound query, so recovery can never silently change the answer the
        caller asked for."""
        if bindings is not None:
            if not hasattr(query_fn, "bind"):
                raise TypeError(
                    "bindings= requires a parameterized plan template "
                    "(repro.serve.PlanTemplate); got "
                    f"{type(query_fn).__name__}")
            query_fn = query_fn.bind(**bindings)
        policy = self.policy
        factor = self.capacity_factor
        wire_format = self.wire_format
        fn = query_fn
        report = RunReport()
        overflow_failures = transient_failures = 0
        t_start = time.perf_counter()
        for attempt in range(1, policy.max_attempts + 1):
            if self.deadline_s is not None and attempt > 1 and \
                    time.perf_counter() - t_start > self.deadline_s:
                raise QueryTimeout(
                    f"overall deadline {self.deadline_s:.3f}s exceeded "
                    f"after {attempt - 1} attempts "
                    f"({time.perf_counter() - t_start:.3f}s)", report)
            if self.chaos is not None:
                self.chaos.begin_attempt(attempt)
            inference = getattr(fn, "_infer", True) is not False
            rep = AttemptReport(attempt=attempt, outcome="ok", wall_s=0.0,
                                capacity_factor=factor,
                                wire_format=wire_format, inference=inference,
                                devices=self.devices,
                                generation=self.topology_generation)
            report.attempts.append(rep)
            t0 = time.perf_counter()
            try:
                result, stats, overflow, reused = self._attempt(
                    fn, factor, wire_format)
            except Exception as exc:
                rep.wall_s = time.perf_counter() - t0
                rep.error = f"{type(exc).__name__}: {exc}"
                kind = classify_failure(exc)
                rep.outcome = kind.value
                self._note_injected(report)
                if kind is FailureKind.DETERMINISTIC:
                    raise            # a bug: surface on attempt 1, no retries
                if attempt >= policy.max_attempts:
                    raise
                if kind is FailureKind.DEVICE_LOST:
                    # topology-elastic rung: shrink to the survivors and
                    # re-execute — the database re-partitions over N', and
                    # the planner re-derives its analysis for the re-run
                    # (statistics and key_bits are width-invariant; the
                    # per-device budgets re-price through the cluster spec)
                    lost = self._shrink_topology(exc)
                    if not lost:
                        raise    # 1-device mesh: no survivors to shrink onto
                    rep.error += (f" [lost {list(lost)} -> "
                                  f"{self.devices} devices]")
                    replan = getattr(fn, "info", None)
                    if callable(replan):
                        replan(self.db)
                elif kind is FailureKind.CORRUPT:
                    # never trust the failed buffer: conservative format
                    wire_format = "wide"
                else:                # TRANSIENT: bounded backoff
                    transient_failures += 1
                    rep.backoff_s = policy.backoff(
                        transient_failures, seed=self._jitter_seed())
                    time.sleep(rep.backoff_s)
                continue
            rep.wall_s = time.perf_counter() - t0
            rep.snapshots_reused = reused
            self._note_injected(report)
            if overflow:
                rep.outcome = FailureKind.OVERFLOW.value
                if attempt >= policy.max_attempts:
                    break
                factor *= self.escalation   # bigger buffers on re-execution
                overflow_failures += 1
                if overflow_failures >= 2 and \
                        hasattr(query_fn, "with_inference"):
                    # capacity escalation cannot fix a groups_hint that
                    # undercounts the true distinct groups (a plan-author
                    # claim like Q13's, or hints analyzed against stand-in
                    # metadata) NOR a lying wire bound tripping the narrow-
                    # lane range check: after one failed escalation,
                    # recompile with no hints at all — the conservative
                    # program has no hint-induced overflow left (hash-
                    # dictionary group-bys degrade to the single-sort path)
                    # and, with no bounds, every exchange ships at full width
                    fn = query_fn.with_inference(False)
                continue
            if policy.deadline_s is not None and \
                    rep.wall_s > policy.deadline_s and \
                    attempt < policy.max_attempts:
                # straggler: correct but late — speculative re-execution
                rep.outcome = FailureKind.TRANSIENT.value
                rep.error = (f"deadline {policy.deadline_s:.3f}s exceeded "
                             f"({rep.wall_s:.3f}s)")
                continue
            return RunResult(result, stats, attempt, factor,
                             time.perf_counter() - t_start, report)
        raise RuntimeError(
            f"query overflowed at capacity_factor={factor:.1f} "
            f"after {policy.max_attempts} attempts")

    def _note_injected(self, report: RunReport) -> None:
        if self.chaos is not None:
            new = self.chaos.events[len(report.injected):]
            report.injected.extend(new)
            # attribute the injection's cut point to the current attempt row
            if new and report.attempts:
                report.attempts[-1].cut = new[-1].cut


def choose_exchange(cluster: pm.ClusterSpec, v: int, small_bytes: float,
                    large_bytes: float) -> str:
    """Cost-based broadcast-vs-shuffle decision (paper Eq. 3)."""
    return "broadcast" if pm.broadcast_beats_shuffle(
        cluster, v, small_bytes, large_bytes) else "shuffle"


def skew_imbalance(recv_counts: np.ndarray, k: int = 1) -> float:
    """Paper §3.5: max over nodes / mean (k devices per node).

    Validates the shape up front (a ragged ``len(recv_counts) % k`` used to
    surface as an opaque numpy reshape error) and returns the neutral 1.0
    for the empty / single-node edge instead of dividing by a clamped mean.
    """
    recv_counts = np.asarray(recv_counts)
    if k < 1:
        raise ValueError(f"devices-per-node k must be >= 1, got {k}")
    if recv_counts.size % k != 0:
        raise ValueError(
            f"recv_counts has {recv_counts.size} entries, not divisible by "
            f"k={k} devices per node")
    v = recv_counts.size // k
    if v <= 1:
        return 1.0   # nothing to be imbalanced against
    per_node = recv_counts.reshape(v, k).sum(axis=1)
    mean = per_node.mean()
    if mean == 0:
        return 1.0   # no traffic at all
    return float(per_node.max() / mean)


def salt_hot_keys(keys: np.ndarray, n_partitions: int,
                  hot_threshold: float = 4.0) -> np.ndarray:
    """Host-side salting: keys whose frequency exceeds ``hot_threshold`` x the
    mean get a per-row salt so their rows spread over all partitions.  Used
    before grouped shuffles (the merge aggregation is salt-agnostic since the
    final combine runs per full key)."""
    uniq, counts = np.unique(keys, return_counts=True)
    mean = counts.mean()
    hot = set(uniq[counts > hot_threshold * mean].tolist())
    if not hot:
        return keys
    salted = keys.astype(np.int64).copy()
    is_hot = np.isin(keys, list(hot))
    salt = np.arange(is_hot.sum(), dtype=np.int64) % n_partitions
    salted[is_hot] = salted[is_hot] * np.int64(n_partitions) + salt
    return salted
