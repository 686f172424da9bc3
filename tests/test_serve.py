"""Serving-layer tests: templates, signatures, cache, batch, lineage keying.

Layers:

  * **Fingerprint** — the `plan_fingerprint` collision fix: content (columns,
    keys, aggs, literals, DAG wiring, parameter bindings) distinguishes
    plans the old type-name-sequence hash collided, and two bindings of one
    template can never exchange lineage snapshots.
  * **Templates** — domain-sound planner refinement (weakest bound over the
    parameter domain), bind-time domain validation, parameter-spec conflict
    detection.
  * **Cache/server** — one jit trace per template across bindings (the
    recompile gate's ground truth), FIFO bound, and eviction through the
    planner invalidation registry (`stats_override` entry/exit, table
    mutation).
  * **Batch** — the cross-query memo: a mixed interleaved parameterized
    stream through `BatchExecutor` is byte-identical to sequential
    one-query-at-a-time eager execution on both planner legs and both wire
    legs, with genuine cross-query sharing; an overflowing request re-runs
    conservatively without poisoning its neighbours.
"""
import numpy as np
import pytest

from repro import serve
from repro.core import backend as B
from repro.core import plan as P
from repro.core import planner
from repro.core.plan import col, param, scan
from repro.core.planner import (ColStats, params_of, plan_signature,
                                subplan_signatures)
from repro.core.table import days
from repro.data import tpch
from repro.distributed.lineage import LineageStore, plan_fingerprint
from repro.queries import QUERIES

FAST_QIDS = (1, 3, 5, 6, 9, 13, 18)


@pytest.fixture(scope="module")
def db():
    return tpch.generate(0.005, seed=11)


def _requests(qids):
    """Mixed interleaved parameterized stream: every sample of every qid,
    round-robin across queries (template changes request-to-request)."""
    per = [[(serve.TEMPLATES[q], s) for s in serve.TEMPLATES[q].samples]
           for q in qids]
    out, i = [], 0
    while any(per):
        if per[i % len(per)]:
            out.append(per[i % len(per)].pop(0))
        i += 1
    return out


# ---------------------------------------------------------------------------
# fingerprint: content, not shape
# ---------------------------------------------------------------------------

def _shape_twin_a():
    return scan("lineitem").filter(col("l_quantity") < 10) \
        .group_by(["l_returnflag"], [("s", "sum", "l_quantity")],
                  exchange="gather", final=True) \
        .finalize(sort_keys=[("l_returnflag", True)], replicated=True)


def _shape_twin_b():
    # IDENTICAL node-type sequence (Scan/Filter/GroupBy/Finalize) — the old
    # type-name-only fingerprint collided these two
    return scan("lineitem").filter(col("l_discount") < 10) \
        .group_by(["l_linestatus"], [("s", "sum", "l_extendedprice")],
                  exchange="gather", final=True) \
        .finalize(sort_keys=[("l_linestatus", True)], replicated=True)


def test_fingerprint_distinguishes_same_shaped_plans():
    a, b = planner.walk(_shape_twin_a()), planner.walk(_shape_twin_b())
    assert [type(n).__name__ for n in a] == [type(n).__name__ for n in b]
    assert plan_fingerprint(a) != plan_fingerprint(b)
    assert plan_signature(_shape_twin_a()) != plan_signature(_shape_twin_b())


def test_fingerprint_stable_across_rebuilds():
    # two independent builds of the SAME logical plan agree (the property
    # that lets a restarted process resume its own snapshots)
    assert plan_fingerprint(planner.walk(_shape_twin_a())) == \
        plan_fingerprint(planner.walk(_shape_twin_a()))


def test_fingerprint_distinguishes_bindings():
    t = serve.TEMPLATES[1]
    nodes = planner.walk(t.query.plan)
    b0 = t.bind().values
    b1 = t.bind(q1_cutoff=days("1998-08-15")).values
    assert plan_fingerprint(nodes, b0) != plan_fingerprint(nodes, b1)
    # canonical across host scalar types: numpy int == python int
    assert plan_fingerprint(nodes, {"q1_cutoff": np.int64(10448)}) == \
        plan_fingerprint(nodes, {"q1_cutoff": 10448})


def test_fingerprint_distinguishes_dag_sharing():
    # one subtree consumed twice (DAG) vs two equal-content copies (tree):
    # identical content per node, different wiring — walk ordinals differ,
    # so the signatures must too (snapshot tags are walk ordinals)
    def sel():
        return scan("orders").select("o_orderkey", "o_custkey")
    s = sel()
    dag = s.join(s, "o_custkey", "o_orderkey", ["o_orderkey"])
    tree = sel().join(sel(), "o_custkey", "o_orderkey", ["o_orderkey"])
    assert plan_signature(dag) != plan_signature(tree)


def test_bindings_never_exchange_snapshots(db, tmp_path):
    """Two bindings of one template run through one LineageStore directory:
    the second run must NOT resume from the first's snapshots."""
    from repro.distributed.lineage import run_resumable
    t = serve.TEMPLATES[1]
    store = LineageStore(str(tmp_path / "lineage"))
    r_a, _, overflow, reused_a = run_resumable(t.bind(), db, store)
    assert not overflow and reused_a == 0 and store.saved > 0
    # re-running the SAME binding resumes from its snapshots...
    _, _, _, reused_again = run_resumable(t.bind(), db, store)
    assert reused_again > 0
    # ...but a DIFFERENT binding of the same template, same store directory,
    # must miss every one of them and produce ITS answer, not binding A's
    bound_b = t.bind(q1_cutoff=days("1998-08-15"))
    r_b, _, _, reused_b = run_resumable(bound_b, db, store)
    assert reused_b == 0, "cross-binding snapshot reuse: silent wrong answer"
    ref_b, _ = B.run_local(bound_b, db, jit=False)
    for k in ref_b:
        assert np.array_equal(ref_b[k], r_b[k])
    assert not np.array_equal(r_a["count_order"], r_b["count_order"])


# ---------------------------------------------------------------------------
# templates: domain-sound refinement + bind validation
# ---------------------------------------------------------------------------

def test_refinement_uses_weakest_domain_bound(db):
    sch = {"x": ColStats(0, 100, 101)}
    p = param("p", lo=10, hi=20)
    le = planner._refine_filter(col("x") <= p, sch, db)["x"]
    assert (le.lo, le.hi) == (0, 20)     # <= keeps rows up to the domain hi
    ge = planner._refine_filter(col("x") >= p, sch, db)["x"]
    assert (ge.lo, ge.hi) == (10, 100)   # >= keeps rows down to the domain lo
    eq = planner._refine_filter(col("x") == p, sch, db)["x"]
    assert (eq.lo, eq.hi, eq.card) == (10, 20, 11)
    # a domainless parameter refines nothing (conservative, always sound)
    free = planner._refine_filter(col("x") <= param("q"), sch, db)["x"]
    assert (free.lo, free.hi) == (0, 100)
    # a literal still refines exactly as before
    lit = planner._refine_filter(col("x") <= 42, sch, db)["x"]
    assert lit.hi == 42


def test_template_info_sound_for_every_binding(db):
    """One cached PlanInfo serves every binding: claims derived from the
    parameter DOMAINS must hold at the extreme admissible bindings — with
    inference on, the extremes run without overflow (``run_local`` asserts
    it) and match the no-hints execution exactly.  Only Q1's float sums and
    averages, which the hinted direct path reduces masked (a tree) and the
    sort path adds in row order, agree to 1e-12 relative instead."""
    t = serve.TEMPLATES[1]
    lo_dom, hi_dom = t.params["q1_cutoff"].lo, t.params["q1_cutoff"].hi
    tree = {"sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
            "avg_qty", "avg_price", "avg_disc"}
    for cutoff in (lo_dom, hi_dom):
        bound = t.bind(q1_cutoff=cutoff)
        got, s_got = B.run_local(bound.with_inference(True), db, jit=False)
        ref, _ = B.run_local(bound.with_inference(False), db, jit=False)
        assert s_got.agg_reductions.get("masked"), cutoff
        for k in ref:
            if k in tree and np.asarray(ref[k]).dtype.kind == "f":
                assert np.allclose(got[k], ref[k], rtol=1e-12, atol=0), \
                    (cutoff, k)
            else:
                assert np.array_equal(ref[k], got[k]), (cutoff, k)


def test_bind_validation():
    t = serve.TEMPLATES[6]
    with pytest.raises(ValueError, match="unknown parameter"):
        t.bind(nope=3)
    with pytest.raises(ValueError, match="outside its declared domain"):
        t.bind(q6_qty=50)
    with pytest.raises(ValueError, match="int64"):
        t.bind(q6_qty=24.5)
    with pytest.raises(ValueError, match="expected a number"):
        t.bind(q6_qty="24")
    # dtype coercion: integral float binds an int64 param
    assert t.bind(q6_qty=24.0).values["q6_qty"] == 24
    # missing + no default
    bare = serve.PlanTemplate(
        lambda: scan("lineitem").filter(col("l_quantity") < param("k"))
        .agg_scalar([("n", "count", None)]), name="bare")
    with pytest.raises(ValueError, match="no binding and no default"):
        bare.bind()


def test_param_spec_conflict_detected():
    a = param("k", lo=0, hi=10)
    b = param("k", lo=0, hi=99)
    plan = scan("lineitem").filter((col("l_quantity") < a) &
                                   (col("l_linenumber") < b)) \
        .agg_scalar([("n", "count", None)])
    with pytest.raises(ValueError, match="conflicting declarations"):
        params_of(plan)


def test_param_domain_validation():
    with pytest.raises(ValueError, match="both lo and hi"):
        param("p", lo=3)
    with pytest.raises(ValueError, match="empty domain"):
        param("p", lo=5, hi=4)
    with pytest.raises(ValueError, match="unsupported dtype"):
        param("p", dtype="int32")
    assert param("p", lo=0.0, hi=1.0).dtype == "float64"
    assert param("p", lo=0, hi=1).dtype == "int64"


def test_subplan_signatures_content_addressed():
    # the same logical subtree built twice hashes alike (what batch sharing
    # keys on); parameter reachability is per-subtree
    t = serve.TEMPLATES[6]
    subs = subplan_signatures(t.query.plan)
    assert subs[id(t.query.plan)][1] == frozenset(t.params)
    scans = [n for n in planner.walk(t.query.plan)
             if isinstance(n, P.Scan)]
    assert all(subs[id(s)][1] == frozenset() for s in scans)
    twin = subplan_signatures(serve.PlanTemplate(
        serve.templates._q6_template, name="q6twin").query.plan)
    roots_a = {h for h, _ in subs.values()}
    roots_b = {h for h, _ in twin.values()}
    assert roots_a == roots_b


# ---------------------------------------------------------------------------
# compiled-plan cache: one trace per template, FIFO, invalidation
# ---------------------------------------------------------------------------

def test_one_trace_per_template_across_bindings(db):
    srv = serve.QueryServer(db)
    reqs = _requests((1, 6))         # 3 + 3 samples, interleaved
    srv.serve(reqs, infer=True)
    assert srv.recompiles == 2, "re-binding must never re-trace"
    assert srv.cache_hits == len(reqs) - 2
    # a jitted and an eager execution of the same binding agree
    got = srv.submit(6, {"q6_qty": 25}, infer=True)
    ref, _ = B.run_local(
        serve.TEMPLATES[6].bind(q6_qty=25).with_inference(True),
        db, jit=False)
    np.testing.assert_allclose(got["revenue"], ref["revenue"], rtol=1e-9)


def test_plancache_fifo_bound(db):
    cache = serve.PlanCache(max_entries=2)
    cache.put(db, "a", 1)
    cache.put(db, "b", 2)
    cache.put(db, "c", 3)            # evicts "a" (FIFO)
    assert cache.get(db, "a") is None
    assert cache.get(db, "b") == 2 and cache.get(db, "c") == 3
    assert len(cache) == 2 and cache.evictions == 1


def test_stats_override_evicts_compiled_templates(db):
    srv = serve.QueryServer(db)
    srv.submit(6, infer=True)
    assert srv.recompiles == 1 and len(srv.cache) == 1
    with planner.stats_override(db, {}):
        # entry invalidated: serving inside the scope must recompile against
        # the overridden statistics
        assert len(srv.cache) == 0
        srv.submit(6, infer=True)
        assert srv.recompiles == 2
    # exit invalidated too: the scope's program must not serve real traffic
    assert len(srv.cache) == 0
    srv.submit(6, infer=True)
    assert srv.recompiles == 3


def test_table_mutation_evicts_compiled_templates():
    db2 = tpch.generate(0.002, seed=3)
    srv = serve.QueryServer(db2)
    before = srv.submit(6, infer=True)
    assert srv.recompiles == 1
    # the documented mutation protocol: change tables, then invalidate_stats
    li = db2.tables["lineitem"]
    li["l_quantity"] = np.minimum(np.asarray(li["l_quantity"]), 10)
    planner.invalidate_stats(db2)
    assert len(srv.cache) == 0, "stale template would serve wrong answers"
    srv2 = serve.QueryServer(db2)   # tables snapshot taken at server build
    after = srv2.submit(6, infer=True)
    assert srv2.recompiles == 1
    assert not np.array_equal(before["revenue"], after["revenue"])


def test_invalidation_scoped_to_the_database(db):
    db2 = tpch.generate(0.002, seed=3)
    srv = serve.QueryServer(db)
    srv.submit(6, infer=True)
    planner.invalidate_stats(db2)    # a DIFFERENT database
    assert len(srv.cache) == 1, "foreign invalidation must not evict"


# ---------------------------------------------------------------------------
# batch executor: differential vs sequential + sharing + overflow isolation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("infer,wire", [(True, "narrow"), (True, "wide"),
                                        (False, "narrow")])
def test_batch_differential_fast(db, infer, wire):
    """Mixed interleaved parameterized stream through the batch executor ==
    sequential one-query-at-a-time eager execution, byte-identical, on both
    planner legs and both wire legs."""
    reqs = _requests(FAST_QIDS)
    bx = serve.BatchExecutor(db, wire_format=wire)
    got = bx.run_batch(reqs, infer=infer)
    assert bx.shared_hits > 0, "no cross-query sharing happened"
    for (t, s), out in zip(reqs, got):
        ref, _ = B.run_local(t.bind(**s).with_inference(infer), db,
                             jit=False, wire_format=wire)
        assert set(ref) == set(out), t.name
        for k in ref:
            assert np.array_equal(ref[k], out[k]), (t.name, k, infer, wire)


@pytest.mark.slow
@pytest.mark.parametrize("infer", [True, False])
def test_batch_differential_all22(db, infer):
    reqs = _requests(range(1, 23))
    bx = serve.BatchExecutor(db)
    got = bx.run_batch(reqs, infer=infer)
    for (t, s), out in zip(reqs, got):
        ref, _ = B.run_local(t.bind(**s).with_inference(infer), db,
                             jit=False)
        for k in ref:
            assert np.array_equal(ref[k], out[k]), (t.name, k)


def _lying_template():
    """groups_hint=2 undercounts orders wildly: the hash-compaction
    dictionary overflows at any sane capacity factor."""
    g = scan("orders").group_by(["o_custkey", "o_orderkey"],
                                [("n", "count", None)],
                                exchange="gather", final=True, groups_hint=2)
    return g.finalize(replicated=True)


def test_server_overflow_recovers_conservatively(db):
    lying = serve.PlanTemplate(_lying_template, name="lying")
    srv = serve.QueryServer(db)
    out = srv.submit(lying, infer=True)
    assert srv.overflow_reruns == 1
    # one row per order, correct despite the lying claim
    assert out["n"].size == np.asarray(
        db.tables["orders"]["o_orderkey"]).size
    assert (out["n"] >= 1).all()


def test_server_build_key_outside_planned_range_reruns():
    """Tables mutated after the planner proved a join's build-key range
    (stale statistics): the direct-address index sees a valid key outside
    the range and raises overflow; the server counts one conservative rerun,
    whose sorted index gives the reference answer."""
    db2 = tpch.generate(0.002, seed=5)
    t12 = serve.TEMPLATES[12]
    assert planner.column_stats(db2)          # statistics proven now
    orders, li = db2.tables["orders"], db2.tables["lineitem"]
    ok, lk = np.asarray(orders["o_orderkey"]), np.asarray(li["l_orderkey"])
    hi = int(ok.max())
    # move the orders holding Q12's qualifying lineitems above the range
    moved = np.unique(lk[np.isin(np.asarray(li["l_shipmode"]),
                                 db2.codes("l_shipmode", ["MAIL", "SHIP"]))])
    moved = moved[:20]
    remap = {int(k): hi + 1 + i for i, k in enumerate(moved)}
    orders["o_orderkey"] = np.asarray([remap.get(int(k), k) for k in ok],
                                      dtype=ok.dtype)
    li["l_orderkey"] = np.asarray([remap.get(int(k), k) for k in lk],
                                  dtype=lk.dtype)
    srv = serve.QueryServer(db2)      # tables snapshot taken at server build
    out = srv.submit(t12, infer=True)
    assert srv.overflow_reruns == 1
    assert srv.index_builds(t12, infer=True) == {"direct": 1}
    assert srv.index_builds(t12, rerun=True) == {"sorted": 1}
    ref, _ = B.run_reference(t12.bind(), db2)
    assert set(out) == set(ref)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k], np.float64),
                                   np.asarray(ref[k], np.float64),
                                   rtol=1e-12, err_msg=k)


def test_batch_overflow_isolated(db):
    """A lying request re-runs conservatively; its neighbours (before AND
    after it in the batch) stay byte-identical to sequential execution."""
    lying = serve.PlanTemplate(_lying_template, name="lying")
    t6, t1 = serve.TEMPLATES[6], serve.TEMPLATES[1]
    reqs = [(t6, {}), (lying, {}), (t1, {"q1_cutoff": days("1998-08-15")})]
    bx = serve.BatchExecutor(db)
    got = bx.run_batch(reqs, infer=True)
    assert bx.overflow_reruns == 1
    assert got[1]["n"].size == np.asarray(
        db.tables["orders"]["o_orderkey"]).size
    for (t, s), out in ((reqs[0], got[0]), (reqs[2], got[2])):
        ref, _ = B.run_local(t.bind(**s).with_inference(True), db, jit=False)
        for k in ref:
            assert np.array_equal(ref[k], out[k]), (t.name, k)


# ---------------------------------------------------------------------------
# fault runner integration
# ---------------------------------------------------------------------------

def test_query_runner_accepts_template_bindings(db, tmp_path):
    from repro.distributed.fault import QueryRunner
    runner = QueryRunner(db, None,
                         lineage=LineageStore(str(tmp_path / "ln")))
    runner.chaos = None              # pin: no env-leg injection here
    rr = runner.run(serve.TEMPLATES[6],
                    bindings={"q6_disc_lo": 0.03, "q6_disc_hi": 0.05})
    ref, _ = B.run_local(
        serve.TEMPLATES[6].bind(q6_disc_lo=0.03, q6_disc_hi=0.05),
        db, jit=False)
    np.testing.assert_allclose(rr.result["revenue"], ref["revenue"],
                               rtol=1e-9)
    with pytest.raises(TypeError, match="plan template"):
        runner.run(QUERIES[6], bindings={"q6_qty": 24})


def test_default_bindings_match_literal_queries(db):
    """samples[0] (all defaults) reproduces the literal query byte-for-byte
    for every parameterized template."""
    for qid in (1, 3, 5, 6):
        t = serve.TEMPLATES[qid]
        ref, _ = B.run_local(QUERIES[qid].with_inference(False), db,
                             jit=False)
        got, _ = B.run_local(t.bind().with_inference(False), db, jit=False)
        for k in ref:
            assert np.array_equal(ref[k], got[k]), (qid, k)
