"""Distributed (8 virtual devices) tests — run in a subprocess so the
device-count XLA flag never leaks into the main test process."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


_PRELUDE = """
import numpy as np, jax
from repro.core import backend as B
from repro.core.compat import make_mesh
from repro.data import tpch
from repro.queries import QUERIES
mesh = make_mesh((8,), ("data",))
db = tpch.generate(0.005, seed=11)
def check(qid, **kw):
    r_ref, _ = B.run_reference(QUERIES[qid], db)
    r_dist, stats, ov = B.run_distributed(QUERIES[qid], db, mesh,
                                          capacity_factor=3.0, **kw)
    assert not ov, f"q{qid} overflow"
    n = len(next(iter(r_ref.values())))
    for k in set(r_ref) & set(r_dist):
        assert len(r_dist[k]) == n, (qid, k, len(r_dist[k]), n)
        np.testing.assert_allclose(np.asarray(r_dist[k], np.float64),
                                   np.asarray(r_ref[k], np.float64),
                                   rtol=1e-7, err_msg=f"q{qid} {k}")
    return stats
"""


@pytest.mark.slow
def test_distributed_queries_exchange_heavy():
    """The exchange-heavy plans: shuffles, broadcasts, left join, allreduce."""
    out = _run(_PRELUDE + """
for qid in (1, 3, 9, 10, 13, 16, 18, 22):
    check(qid)
    print("q%d ok" % qid)
""")
    assert out.count("ok") == 8


@pytest.mark.slow
def test_distributed_all_22_planner_path():
    """Every builder plan runs SPMD and matches the NumPy reference, with
    runtime exchange counts equal to the IR-derived static counts — the full
    three-backend acceptance sweep for the planner path."""
    out = _run(_PRELUDE + """
for qid in sorted(QUERIES):
    stats = check(qid)
    assert stats.counts() == QUERIES[qid].static_counts(), (
        qid, stats.counts(), QUERIES[qid].static_counts())
    print("q%d ok" % qid)
""", timeout=2400)
    assert out.count("ok") == 22


@pytest.mark.slow
def test_distributed_wire_narrow_equals_wide_all22():
    """ISSUE 4 acceptance: the stats-narrowed wire format is byte-identical
    to the wide format on every query, on real 8-device exchanges, with no
    overflow, on BOTH planner legs (inference off -> no bounds -> narrow
    degenerates to wide by construction, asserted equal all the same)."""
    out = _run(_PRELUDE + """
# inference ON: all 22 plans; inference OFF: a sample — with no bounds the
# narrow format degenerates to wide by construction, so the interesting
# surface is the hinted leg
for infer, qids in ((True, sorted(QUERIES)), (False, [1, 5, 9, 13, 18])):
    for qid in qids:
        q = QUERIES[qid].with_inference(infer)
        r_n, s_n, ov_n = B.run_distributed(q, db, mesh, capacity_factor=3.0,
                                           wire_format="narrow")
        r_w, s_w, ov_w = B.run_distributed(q, db, mesh, capacity_factor=3.0,
                                           wire_format="wide")
        assert not ov_n and not ov_w, (qid, infer)
        assert set(r_n) == set(r_w), (qid, infer)
        for k in r_n:
            np.testing.assert_array_equal(r_n[k], r_w[k],
                                          err_msg="q%d %s" % (qid, k))
        if infer:
            assert sum(e.message_bytes for e in s_n.log) <= \
                sum(e.message_bytes for e in s_w.log), qid
        print("q%d infer=%s ok" % (qid, infer))
""", timeout=4800)
    assert out.count("ok") == 27


@pytest.mark.slow
def test_distributed_wire_stats_match_static_all22():
    """Runtime ExchangeStats wire descriptors == the IR derivation on the
    distributed backend, all 22 queries (Ref/Local legs are fast tests)."""
    out = _run(_PRELUDE + """
from repro.core import planner as PL
for qid in sorted(QUERIES):
    _, stats, ov = B.run_distributed(QUERIES[qid], db, mesh,
                                     capacity_factor=3.0,
                                     wire_format="narrow")
    assert not ov, qid
    got = [(e.kind, e.wire, e.row_wire_bytes, e.row_logical_bytes)
           for e in stats.log]
    want = [(d["kind"], d["wire"], d["row_wire_bytes"],
             d["row_logical_bytes"])
            for d in QUERIES[qid].static_wire(db, narrow=True)]
    assert got == want, (qid, got, want)
    print("q%d ok" % qid)
""", timeout=2400)
    assert out.count("ok") == 22


@pytest.mark.slow
def test_distributed_per_column_exchange_matches_packed():
    """Paper-faithful per-column exchange == packed fused exchange."""
    _run(_PRELUDE + """
s_packed = check(9, packed_exchange=True)
s_col = check(9, packed_exchange=False)
# same logical plan, more collectives in per-column mode
packed_ops = sum(e.collectives for e in s_packed.log)
col_ops = sum(e.collectives for e in s_col.log)
assert col_ops > packed_ops, (col_ops, packed_ops)
print("collectives packed=%d per-column=%d" % (packed_ops, col_ops))
""")


@pytest.mark.slow
def test_distributed_broadcast_p2p_variant():
    """§7.1: p2p-emulated broadcast gives identical results (and more traffic)."""
    _run(_PRELUDE + """
import jax.numpy as jnp
from repro.core.table import Database
def q(ctx):
    c = ctx.scan("customer")
    cb = ctx.broadcast(ctx.select(c, "c_custkey", "c_acctbal"), p2p=True)
    g = ctx.group_by(cb, ["c_custkey"], [("n", "count", None)],
                     exchange="local")
    s = ctx.agg_scalar(g, [("total", "sum", "n")])
    return {"total": s["total"]}
r_ref, _ = B.run_reference(q, db)
r_dist, stats, ov = B.run_distributed(q, db, mesh)
# broadcast replicates: every device sees all customers exactly once
assert int(r_dist["total"][0]) == 8 * int(r_ref["total"][0]), (r_dist, r_ref)
kinds = [e.kind for e in stats.log]
assert "broadcast_p2p" in kinds
print("p2p broadcast ok", kinds)
""")


@pytest.mark.slow
def test_skewed_jcch_runs_and_matches():
    """JCC-H skew: correctness preserved, skew visible in partition sizes."""
    _run("""
import numpy as np, jax
from repro.core import backend as B
from repro.core.compat import make_mesh
from repro.data import jcch
from repro.queries import QUERIES
mesh = make_mesh((8,), ("data",))
db = jcch.generate(0.005, seed=11, skew=0.3)
# partitioning by the SKEWED foreign key exposes the imbalance the paper's
# Fig 20 reports (unique-PK partitioning stays balanced by construction)
parts, _ = B.partition_database(db, 8,
                                partition_keys={"lineitem": "l_partkey"})
counts = parts["lineitem"]["__count"]
imb = counts.max() / counts.mean()
uni = jcch.generate(0.005, seed=11, skew=0.0)
parts_u, _ = B.partition_database(uni, 8,
                                  partition_keys={"lineitem": "l_partkey"})
cu = parts_u["lineitem"]["__count"]
imb_u = cu.max() / cu.mean()
assert imb > imb_u + 0.05, (imb, imb_u)
for qid in (4, 13):
    r_ref, _ = B.run_reference(QUERIES[qid], db)
    r_dist, _, ov = B.run_distributed(QUERIES[qid], db, mesh,
                                      capacity_factor=4.0)
    assert not ov
    for k in set(r_ref) & set(r_dist):
        np.testing.assert_allclose(np.asarray(r_dist[k], np.float64),
                                   np.asarray(r_ref[k], np.float64), rtol=1e-7)
print("jcch ok, lineitem imbalance=%.2f" % imb)
""")


@pytest.mark.slow
def test_fault_runner_escalates_capacity():
    _run("""
import numpy as np, jax
from repro.core import backend as B
from repro.core.compat import make_mesh
from repro.data import tpch
from repro.distributed.fault import QueryRunner
from repro.queries import QUERIES
mesh = make_mesh((8,), ("data",))
db = tpch.generate(0.005, seed=11)
# absurdly small starting factor forces overflow -> escalation
runner = QueryRunner(db, mesh, capacity_factor=0.05, max_attempts=8)
res = runner.run(QUERIES[13])
assert res.attempts > 1, "expected at least one overflow retry"
r_ref, _ = B.run_reference(QUERIES[13], db)
np.testing.assert_allclose(np.asarray(res.result["custdist"], np.float64),
                           np.asarray(r_ref["custdist"], np.float64))
print("fault runner ok: attempts=%d factor=%.2f" % (res.attempts,
                                                    res.capacity_factor))
""")


@pytest.mark.slow
def test_sf1000_plan_compiles():
    """The paper's workload at SF=1000 lowers+compiles (shape-only)."""
    _run("""
import jax, numpy as np
from repro.core.compat import make_mesh
from repro.data import tpch
from repro.launch import dryrun_analytics as da
db = tpch.generate(0.001, seed=7)
db.scale = 1000.0
mesh = make_mesh((8,), ("data",))
rec = da.dryrun_query(6, db, mesh)
assert rec["plan"]["allreduces"] == 1
assert rec["hlo_bytes"] > 0
rec9 = da.dryrun_query(9, db, mesh)
assert rec9["plan"]["shuffles"] == 1 and rec9["plan"]["broadcasts"] == 2
print("sf1000 compile ok: q6 m=%.1fms q9 m=%.1fms" % (
    rec["roofline"]["memory_s"]*1e3, rec9["roofline"]["memory_s"]*1e3))
""", timeout=1200)


def test_run_distributed_reads_mutated_tables():
    """run_distributed partitions the tables as they stand at each call:
    after the documented mutation protocol (change the tables, then
    invalidate_stats) it answers from the new data."""
    import numpy as np
    from repro.core import backend as B, planner
    from repro.core.compat import make_mesh
    from repro.data import tpch
    from repro.queries import QUERIES
    db = tpch.generate(0.002, seed=3)
    mesh = make_mesh((1,), ("data",))
    before, _, _ = B.run_distributed(QUERIES[6], db, mesh)
    li = db.tables["lineitem"]
    li["l_quantity"] = np.minimum(np.asarray(li["l_quantity"]), 10)
    planner.invalidate_stats(db)
    after, _, _ = B.run_distributed(QUERIES[6], db, mesh)
    want, _ = B.run_reference(QUERIES[6], db)
    assert not np.array_equal(before["revenue"], after["revenue"])
    np.testing.assert_allclose(after["revenue"], want["revenue"], rtol=1e-7)
