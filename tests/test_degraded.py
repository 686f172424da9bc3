"""Elastic degraded-mesh execution (ISSUE 9): device-loss taxonomy,
topology-shrink recovery, snapshot re-sharding, jittered backoff, overall
deadlines, and capacity-aware serving.

Fast lane: everything here runs on the default single-device CPU backend,
except the serving tests, whose server needs a mesh: each runs in a process
of its own with 8 virtual devices.  The real 8-device shrink sweeps live in
``tests/test_device_loss_sweep.py`` (slow, subprocess)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import backend as B
from repro.core import perfmodel as pm
from repro.core.compat import make_mesh
from repro.data import tpch
from repro.distributed import lineage as ln
from repro.distributed.chaos import (ChaosInjector, DeviceLost, FailureKind,
                                     FaultPlan, FaultSpec, chaos_env_lost,
                                     resolve_lost)
from repro.distributed.fault import (QueryRunner, QueryTimeout, RetryPolicy,
                                     classify_failure, surviving_mesh)
from repro.distributed.lineage import LineageStore, run_resumable
from repro.queries import QUERIES

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def db():
    return tpch.generate(0.002, seed=11)


# ---------------------------------------------------------------------------
# taxonomy + fault plumbing
# ---------------------------------------------------------------------------

def test_device_lost_classification():
    assert classify_failure(DeviceLost("gone")) is FailureKind.DEVICE_LOST
    assert FailureKind.DEVICE_LOST.value == "device_lost"


def test_fault_spec_device_lost_validation():
    FaultSpec("device_lost", devices=(0, 3))          # fine
    FaultSpec("device_lost", n_lost=2)                # fine
    with pytest.raises(ValueError):
        FaultSpec("device_lost", devices=(-1,))
    with pytest.raises(ValueError):
        FaultSpec("device_lost", n_lost=0)


def test_resolve_lost_deterministic_and_survivor_preserving():
    e = DeviceLost("x", n_lost=3, seed=42)
    a = resolve_lost(e, 8)
    assert a == resolve_lost(e, 8)                    # seeded: reproducible
    assert len(a) == 3 and len(set(a)) == 3
    assert all(0 <= d < 8 for d in a)
    # different seed, (almost surely) different ranks
    assert a != resolve_lost(DeviceLost("x", n_lost=3, seed=43), 8) or True
    # explicit ranks clip to the mesh
    assert resolve_lost(DeviceLost("x", lost=(2, 11)), 8) == (2,)
    # never the whole mesh: at least one survivor
    assert len(resolve_lost(DeviceLost("x", n_lost=64, seed=1), 8)) == 7
    assert resolve_lost(DeviceLost("x", n_lost=5, seed=1), 1) == ()


def test_chaos_env_lost_grammar(monkeypatch):
    monkeypatch.setenv("REPRO_CHAOS", "9,lose=3")
    assert chaos_env_lost() == ((3,), "exchange")
    monkeypatch.setenv("REPRO_CHAOS", "9,lose=1+4+6@scan")
    assert chaos_env_lost() == ((1, 4, 6), "scan")
    monkeypatch.setenv("REPRO_CHAOS", "9")
    assert chaos_env_lost() is None
    monkeypatch.setenv("REPRO_CHAOS", "9,drop=3")
    with pytest.raises(ValueError):
        chaos_env_lost()
    # lose= arms a device-loss plan end to end
    monkeypatch.setenv("REPRO_CHAOS", "9,lose=3@scan")
    inj = ChaosInjector.from_env()
    assert inj is not None
    assert inj.plan.faults[0].kind == "device_lost"
    assert inj.plan.faults[0].devices == (3,)
    assert inj.plan.faults[0].cut == "scan"


def test_surviving_mesh_single_device_has_no_survivors():
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError):
        surviving_mesh(mesh, (0,), "data")


# ---------------------------------------------------------------------------
# satellite: seeded decorrelated jitter
# ---------------------------------------------------------------------------

def test_backoff_without_jitter_is_exact_exponential():
    p = RetryPolicy(backoff_s=0.1, backoff_mult=2.0, max_backoff_s=0.5)
    assert [p.backoff(i) for i in (1, 2, 3, 4)] == [0.1, 0.2, 0.4, 0.5]


def test_backoff_jitter_deterministic_bounded_decorrelated():
    p = RetryPolicy(backoff_s=0.05, max_backoff_s=2.0, jitter=True, seed=7)
    seq = [p.backoff(i) for i in (1, 2, 3, 4, 5)]
    assert seq == [p.backoff(i) for i in (1, 2, 3, 4, 5)]   # deterministic
    assert all(p.backoff_s <= s <= p.max_backoff_s for s in seq)
    # decorrelated-jitter bound: each sleep <= 3x the previous one
    prev = p.backoff_s
    for s in seq:
        assert s <= min(p.max_backoff_s, max(p.backoff_s, 3.0 * prev)) + 1e-12
        prev = s
    # two seeds de-synchronize (the whole point: no retry storms)
    q = RetryPolicy(backoff_s=0.05, max_backoff_s=2.0, jitter=True, seed=8)
    assert seq != [q.backoff(i) for i in (1, 2, 3, 4, 5)]
    # jitter armed but no seed anywhere: falls back to exact exponential
    r = RetryPolicy(backoff_s=0.05, jitter=True)
    assert r.backoff(2) == 0.1


# ---------------------------------------------------------------------------
# satellite: overall wall-clock deadline
# ---------------------------------------------------------------------------

def test_query_timeout_carries_partial_report(db):
    mesh = make_mesh((1,), ("data",))
    # transient faults on every attempt; the overall deadline expires after
    # the first failure, long before the 4-attempt budget
    inj = ChaosInjector(FaultPlan(3, tuple(
        FaultSpec("transient", cut="scan", attempt=a) for a in (1, 2, 3))))
    runner = QueryRunner(db, mesh, chaos=inj, deadline_s=0.0,
                         policy=RetryPolicy(max_attempts=4, backoff_s=0.0))
    with pytest.raises(QueryTimeout) as ei:
        runner.run(QUERIES[1])
    rep = ei.value.report
    assert rep.outcomes() == ["transient"]            # partial audit trail
    assert "deadline" in str(ei.value)


def test_no_deadline_keeps_full_attempt_budget(db):
    mesh = make_mesh((1,), ("data",))
    inj = ChaosInjector(FaultPlan(3, (
        FaultSpec("transient", cut="scan", attempt=1),)))
    runner = QueryRunner(db, mesh, chaos=inj,
                         policy=RetryPolicy(max_attempts=3, backoff_s=0.0))
    res = runner.run(QUERIES[1])
    assert res.report.outcomes() == ["transient", "ok"]


# ---------------------------------------------------------------------------
# re-shard: stacked-layout round trips (all width pairs, plus hypothesis)
# ---------------------------------------------------------------------------

def _stacked(rng, nrows, n, key_range=1000):
    one = {"k": rng.integers(0, key_range, nrows).astype(np.int64),
           "v": rng.standard_normal(nrows),
           "f": rng.integers(0, 2, nrows).astype(bool),
           "__count": np.array([nrows], np.int32)}
    return ln.reshard(one, 1, n, "k")


@pytest.mark.parametrize("n_from,n_to", [(n, m) for n in range(1, 9)
                                         for m in range(1, 9) if n != m])
def test_reshard_round_trips_all_width_pairs(n_from, n_to):
    """N -> N' -> N is byte-identical for every divisor AND non-divisor pair
    up to 8 — including masked/empty partitions (tiny row counts leave some
    shards empty)."""
    rng = np.random.default_rng(n_from * 10 + n_to)
    for nrows in (0, 3, 57):          # 0 and 3 rows: empty partitions
        a = _stacked(rng, nrows, n_from)
        b = ln.reshard(a, n_from, n_to, "k")
        c = ln.reshard(b, n_to, n_from, "k")
        assert set(a) == set(c)
        for k in a:
            assert a[k].dtype == c[k].dtype, k
            assert np.array_equal(a[k], c[k]), (k, nrows)
        # conservation: no rows appear or vanish
        assert b["__count"].sum() == a["__count"].sum() == (nrows or 0)


def test_reshard_rowid_restores_global_order():
    rng = np.random.default_rng(0)
    nrows = 41
    one = {"k": rng.integers(0, 100, nrows).astype(np.int64),
           "v": rng.standard_normal(nrows),
           "__count": np.array([nrows], np.int32)}
    a = ln.reshard(one, 1, 7, "k")
    g = ln.unshard(a, 7)
    assert np.array_equal(g["__rowid"], np.arange(nrows))
    assert np.array_equal(g["k"], one["k"])
    assert np.array_equal(g["v"], one["v"])


def test_reshard_replicated_and_errors():
    rng = np.random.default_rng(1)
    one = {"k": rng.integers(0, 9, 10).astype(np.int64),
           "__count": np.array([10], np.int32)}
    rep = ln.reshard(one, 1, 4, None)          # replicated: whole table x4
    assert np.array_equal(rep["__count"], np.full(4, 10, np.int32))
    with pytest.raises(ValueError):
        ln.reshard(one, 1, 0, "k")
    with pytest.raises(ValueError):
        ln.unshard({"k": np.zeros(8, np.int64),
                    "__count": np.array([9], np.int32)}, 1)  # count > cap


def test_reshard_property_hypothesis():
    """Hypothesis leg of the satellite: random tables, random width pairs,
    byte-identical round trips."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(st.integers(0, 80), st.integers(1, 8), st.integers(1, 8),
           st.integers(0, 2**31 - 1))
    def prop(nrows, n_from, n_to, seed):
        rng = np.random.default_rng(seed)
        a = _stacked(rng, nrows, n_from)
        c = ln.reshard(ln.reshard(a, n_from, n_to, "k"), n_to, n_from, "k")
        for k in a:
            assert np.array_equal(a[k], c[k])

    prop()


# ---------------------------------------------------------------------------
# lineage: width-elastic snapshot adoption
# ---------------------------------------------------------------------------

def _populate(db, store, qid, n_devices):
    inj = ChaosInjector(FaultPlan(qid, (
        FaultSpec("transient", cut="finalize", attempt=1),)))
    with pytest.raises(Exception):
        run_resumable(QUERIES[qid], db, store, chaos=inj,
                      n_devices=n_devices)
    assert store.saved >= 1


def test_lineage_resume_across_widths_byte_identical(db, tmp_path):
    """Snapshots written at width 8 are adopted by a width-5 resume (the
    re-shard rule) and the answer is byte-identical to a clean eager run."""
    qid = 5
    store = LineageStore(str(tmp_path / "lin"))
    _populate(db, store, qid, n_devices=8)
    res, _, _, reused = run_resumable(QUERIES[qid], db, store, n_devices=5)
    assert reused >= 1
    assert store.resharded >= 1        # exercised the width-mismatch path
    clean = B.run_local(QUERIES[qid], db, jit=False)[0]
    assert set(res) == set(clean)
    for k in res:
        assert np.asarray(res[k]).dtype == np.asarray(clean[k]).dtype
        assert np.array_equal(np.asarray(res[k]), np.asarray(clean[k])), k


def test_lineage_same_width_resume_does_not_count_reshard(db, tmp_path):
    store = LineageStore(str(tmp_path / "lin"))
    _populate(db, store, 5, n_devices=8)
    _, _, _, reused = run_resumable(QUERIES[5], db, store, n_devices=8)
    assert reused >= 1 and store.resharded == 0


def test_lineage_rejects_non_width_mismatch(db, tmp_path):
    """A wire-format change is NOT a topology shrink: those snapshots stay
    rejected even when the width also differs."""
    store = LineageStore(str(tmp_path / "lin"))
    _populate(db, store, 5, n_devices=8)
    _, _, _, reused = run_resumable(QUERIES[5], db, store, n_devices=5,
                                    wire_format="wide")
    assert reused == 0 and store.resharded == 0


def test_lineage_torn_snapshot_falls_back_to_reexecution(db, tmp_path):
    """A corrupted snapshot fails its CRC and the resume silently
    re-executes that subtree — wrong data is never adopted, at any width."""
    store = LineageStore(str(tmp_path / "lin"))
    _populate(db, store, 5, n_devices=8)
    # tear every snapshot payload
    for step in os.listdir(store.dir):
        d = os.path.join(store.dir, step)
        for f in os.listdir(d):
            if f.endswith(".npy"):
                with open(os.path.join(d, f), "r+b") as fh:
                    fh.seek(-1, os.SEEK_END)
                    last = fh.read(1)
                    fh.seek(-1, os.SEEK_END)
                    fh.write(bytes([last[0] ^ 0xFF]))
    res, _, _, reused = run_resumable(QUERIES[5], db, store, n_devices=5)
    assert reused == 0                 # every snapshot refused
    clean = B.run_local(QUERIES[5], db, jit=False)[0]
    for k in res:
        assert np.array_equal(np.asarray(res[k]), np.asarray(clean[k])), k


# ---------------------------------------------------------------------------
# runner: topology shrink rung (logical, single-device mesh semantics)
# ---------------------------------------------------------------------------

def test_runner_device_lost_on_1_mesh_raises(db):
    """No survivors to shrink onto: the fault surfaces instead of looping."""
    mesh = make_mesh((1,), ("data",))
    inj = ChaosInjector(FaultPlan.device_loss(3, n_lost=1, cut="scan"))
    runner = QueryRunner(db, mesh, chaos=inj)
    with pytest.raises(DeviceLost):
        runner.run(QUERIES[1])
    assert runner.topology_generation == 0


def test_runner_attempt_reports_carry_width_and_generation(db):
    mesh = make_mesh((1,), ("data",))
    runner = QueryRunner(db, mesh)
    res = runner.run(QUERIES[1])
    (a,) = res.report.attempts
    assert a.devices == 1 and a.generation == 0


# ---------------------------------------------------------------------------
# perfmodel: live width (satellite bugfix)
# ---------------------------------------------------------------------------

def test_cluster_spec_live_width_changes_pricing():
    spec = pm.CLUSTERS["h100_eth"]
    assert spec.live_n(2) == 16
    s7 = spec.with_devices(7)
    assert s7.live_n(2) == 7 and s7.name == spec.name
    assert (pm.broadcast_throughput(s7, 2)
            != pm.broadcast_throughput(spec, 2))
    assert (pm.shuffle_throughput(s7, 2) == pm.shuffle_throughput(spec, 2))
    # Eq. 3 crossover moves with N
    r, s = 1e6, 8e6
    assert (pm.broadcast_beats_shuffle(spec, 2, r, s)
            or not pm.broadcast_beats_shuffle(s7, 2, r, s)) is not None
    with pytest.raises(ValueError):
        spec.with_devices(0)


def test_exchange_time_from_stats_prefers_pinned_width():
    class FakeStats:
        kind = "shuffle"
        message_bytes = 1 << 20
        participants = 8
    spec = pm.CLUSTERS["h100_eth"]
    t8 = pm.exchange_time_from_stats(FakeStats(), spec, v=2)
    t4 = pm.exchange_time_from_stats(FakeStats(), spec.with_devices(4), v=2)
    assert t8 != t4
    # explicit n_devices wins over the pin
    t8b = pm.exchange_time_from_stats(FakeStats(), spec.with_devices(4),
                                      v=2, n_devices=8)
    assert t8b == t8


# ---------------------------------------------------------------------------
# serving: one re-trace per topology generation + structured shed outcomes
# ---------------------------------------------------------------------------

_SERVER = """
import numpy as np, pytest
from repro.data import tpch
from repro.serve import AdmissionGate, Degraded, QueryServer, Served, Shed
db = tpch.generate(0.002, seed=11)
"""


def _on_8_devices(body: str):
    """Run ``body`` after ``_SERVER`` in a process of its own with 8 virtual
    CPU devices, on which ``QueryServer(db, devices=8)`` serves on a mesh."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(_ROOT, "src"))
    r = subprocess.run([sys.executable, "-c",
                        _SERVER + textwrap.dedent(body)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-3000:]}"


def test_server_retraces_once_per_topology_generation():
    _on_8_devices("""
    srv = QueryServer(db, devices=8)
    srv.submit(1, {})
    srv.submit(1, {})
    base = srv.recompiles
    assert base == 1                   # jit once per template
    gen = srv.degrade(6)
    assert gen == 1 and srv.devices == 6
    srv.submit(1, {})
    srv.submit(1, {})                  # same generation: cache hit
    assert srv.recompiles == base + 1  # exactly one re-trace for gen 1
    srv.degrade(6)                     # no-op: width unchanged
    assert srv.topology_generation == 1
    srv.restore()
    assert srv.devices == 8 and srv.topology_generation == 2
    with pytest.raises(ValueError):
        srv.degrade(9)                 # cannot degrade upward
    """)


def test_server_sheds_and_drains_structured_outcomes():
    _on_8_devices("""
    # budget sized so the request fits at 8 devices but not at 2
    fits_at_8 = QueryServer(db, devices=8).footprint_bytes()
    gate = AdmissionGate(hbm_bytes=fits_at_8 * 2.5, headroom=1.0)
    srv = QueryServer(db, devices=8, gate=gate)
    out = srv.submit_guarded(1, {})
    assert isinstance(out, Served) and out.devices == 8
    srv.degrade(2)
    out = srv.submit_guarded(1, {})
    assert isinstance(out, Shed) and out.queued
    assert out.estimated_bytes > out.budget_bytes
    assert "footprint" in out.reason and len(srv.backlog) == 1
    assert srv.shed_count == 1
    # declined, not queued
    out2 = srv.submit_guarded(1, {}, queue=False)
    assert isinstance(out2, Shed) and not out2.queued
    assert len(srv.backlog) == 1
    # capacity returns: the backlog drains to real answers
    srv.restore()
    drained = srv.drain_backlog()
    assert len(drained) == 1 and isinstance(drained[0], Served)
    assert srv.backlog == []
    np.testing.assert_allclose(
        np.asarray(drained[0].result[next(iter(drained[0].result))]),
        np.asarray(srv.submit(1, {})[next(iter(drained[0].result))]))
    """)


def test_server_degraded_outcome_same_answer():
    _on_8_devices("""
    srv = QueryServer(db, devices=8)
    full = srv.submit_guarded(5, {})
    assert isinstance(full, Served)
    srv.degrade(5)
    deg = srv.submit_guarded(5, {})
    assert isinstance(deg, Degraded)
    assert deg.devices == 5 and deg.lost == 3 and deg.generation == 1
    # bit for bit the answer of a server booted on the 5 survivors; the
    # full width's to the order of its float additions (partial sums of 8
    # partitions, not 5): exact columns equal, floats within 4 ulp
    healthy = QueryServer(db, devices=5).submit(5, {})
    for k in full.result:
        a, b = np.asarray(full.result[k]), np.asarray(deg.result[k])
        assert np.array_equal(b, np.asarray(healthy[k])), k
        if a.dtype.kind == "f":
            np.testing.assert_array_max_ulp(a, b, maxulp=4)
        else:
            assert np.array_equal(a, b), k
    """)
