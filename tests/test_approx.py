"""Statistical verification of the approximate-query ladder (PR 10 tentpole).

Layers:

  * **Sampling** — stratified selection invariants: per-stratum floor of one,
    ceil(n/den) rates, rung nesting (the same row ranks identically at every
    den), determinism in the seed, bookkeeping columns, cache + invalidation
    through the planner registry.
  * **Estimators** — Student-t/normal critical values, honesty gates
    (m < 2 -> infinite half-width, fully-sampled -> zero width).
  * **Monte-Carlo coverage** — the ISSUE gate: for each estimable aggregate
    kind (sum / count / avg) and each sampling rung (1/16..1/2), >= 200
    seeded trials with the true answer inside the reported 95 % CI at
    >= 90 % empirical rate.  Binomial slack: at true coverage 0.95 the
    empirical rate over 200 trials has sd sqrt(.95*.05/200) ~= 1.5 %, so a
    0.90 gate sits > 3 sigma below nominal — a pass is evidence, not luck.
    The 20-trial smoke (tier-1) has sd ~= 4.9 %; its 0.80 gate is the same
    3-sigma slack.  Everything is pinned to ``conftest.APPROX_SEED`` so the
    asserted rates are deterministic numbers, not flaky draws.
  * **Rung-1 identity** — the den == 1 rewrite is a pure scan rename; its
    results are byte-identical to the exact plan on both planner legs and
    both wire formats (the differential leg).
  * **Refusal** — min/max, semi-join-dependent counts, estimates folded into
    scalar arithmetic, grouped estimates feeding a filter/join (q18, SQL
    HAVING), tiny tables: the rewrite returns None and the progressive
    runner falls back to the exact plan (rung 0).  A Select between the site
    and the root keeps the moment columns flowing; finalize raises if a
    scaled result ever arrives without them.
  * **Progressive** — hypothesis property: termination with a final interval
    within tolerance (or the exact top rung), escalations audited as
    TOLERANCE_MISS attempts; the adversarial absent-group case must climb to
    exact rather than fabricate zeros.
  * **Surfacing** — per-rung AttemptReports render rung + CI width in the
    ``--section runs`` audit table; ``QueryServer.submit(tolerance=)`` serves
    off the ladder with rung-keyed cache entries.
"""
import json
import os

import numpy as np
import pytest

from repro.core import backend as B
from repro.core import plan as P
from repro.core import planner
from repro.core.plan import col, scan
from repro.core.table import Database
from repro.data import tpch
from repro.queries import QUERIES
from repro.approx import estimators, progressive, sampling
from repro.approx import rewrite as approx_rewrite
from repro.approx.rewrite import rewrite_for_rung

from conftest import APPROX_SEED

pytestmark = pytest.mark.approx

SMOKE_TRIALS = 20     # tier-1 smoke; the slow sweep runs the full 200
FULL_TRIALS = 200
DENS = (16, 8, 4, 2)  # rung 1 is exact by construction — tested for identity


@pytest.fixture(scope="module")
def db():
    return tpch.generate(0.005, seed=11)


# ---------------------------------------------------------------------------
# sampling invariants
# ---------------------------------------------------------------------------

def test_selection_rates_and_min_one():
    rng = np.random.default_rng(APPROX_SEED)
    g = rng.integers(0, 12, size=3000).astype(np.int64)
    for den in DENS:
        mask, sid, n_g, m_g = sampling.stratified_selection([g], g.size, den)
        np.testing.assert_array_equal(m_g, np.maximum(1, -(-n_g // den)))
        got = np.bincount(sid[mask], minlength=n_g.size)
        np.testing.assert_array_equal(got, m_g)   # exactly m_g rows kept
    # a 1-row stratum survives every rung (floor of one)
    tiny = np.array([0, 1, 1, 1, 1], dtype=np.int64)
    mask, _, n_g, m_g = sampling.stratified_selection([tiny], 5, 16)
    assert m_g[0] == 1 and mask[0]


def test_rungs_nest():
    """A row sampled at rung 1/d stays sampled at every larger rung."""
    rng = np.random.default_rng(APPROX_SEED + 1)
    g = rng.integers(0, 7, size=2000).astype(np.int64)
    masks = {den: sampling.stratified_selection([g], g.size, den)[0]
             for den in (16, 8, 4, 2, 1)}
    assert masks[1].all()
    for small, big in ((16, 8), (8, 4), (4, 2), (2, 1)):
        assert not np.any(masks[small] & ~masks[big])


def test_selection_deterministic_in_seed():
    g = np.zeros(1000, dtype=np.int64)
    a = sampling.stratified_selection([g], 1000, 4, seed=7)[0]
    b = sampling.stratified_selection([g], 1000, 4, seed=7)[0]
    c = sampling.stratified_selection([g], 1000, 4, seed=8)[0]
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_sample_table_bookkeeping():
    rng = np.random.default_rng(APPROX_SEED + 2)
    g = rng.integers(0, 9, size=1500).astype(np.int64)
    cols = {"g": g, "v": rng.normal(size=g.size)}
    s = sampling.sample_table(cols, ("g",), 4)
    n_g = np.bincount(g)
    m_g = np.maximum(1, -(-n_g // 4))
    np.testing.assert_array_equal(s["__sn"], n_g[s["g"]])
    np.testing.assert_array_equal(s["__sm"], m_g[s["g"]])
    np.testing.assert_allclose(s["__sw"],
                               n_g[s["g"]] / m_g[s["g"]], rtol=0)
    assert s["__sw"].dtype == np.float64
    # row order of the base table is preserved (the mask is order-stable)
    mask = sampling.stratified_selection([g], g.size, 4)[0]
    np.testing.assert_array_equal(s["v"], cols["v"][mask])


def test_sample_table_empty_strata():
    """Zero-row tables sample to zero rows — no crash, no fabricated rows."""
    cols = {"g": np.zeros(0, dtype=np.int64), "v": np.zeros(0)}
    s = sampling.sample_table(cols, ("g",), 8)
    assert s["g"].size == 0 and s["__sw"].size == 0


def test_rung_database_cached_and_invalidated():
    rng = np.random.default_rng(APPROX_SEED + 3)
    db2 = Database(tables={"facts": {
        "g": rng.integers(0, 5, 400).astype(np.int64),
        "v": rng.normal(size=400)}}, dicts={}, scale=1.0)
    r1 = sampling.rung_database(db2, "facts", ("g",), 4)
    assert sampling.rung_database(db2, "facts", ("g",), 4) is r1
    assert sampling.rung_name("facts", 4) in r1.tables
    # the rung partitions like its base table
    assert B.PARTITION_KEYS.get(sampling.rung_name("facts", 4)) == \
        B.PARTITION_KEYS.get("facts")
    planner.invalidate_stats(db2)   # the documented mutation protocol
    assert sampling.rung_database(db2, "facts", ("g",), 4) is not r1
    sampling.invalidate(db2)


def test_rung_partition_key_hygiene():
    """An unpartitioned base table must not leave a name -> None mapping in
    the global PARTITION_KEYS (dryrun analytics would read it as replicated),
    and a registered rung entry is dropped with its invalidated rung."""
    rng = np.random.default_rng(APPROX_SEED + 4)
    db2 = Database(tables={"facts": {
        "g": rng.integers(0, 5, 400).astype(np.int64),
        "v": rng.normal(size=400)}}, dicts={}, scale=1.0)
    name = sampling.rung_name("facts", 8)
    try:
        sampling.rung_database(db2, "facts", ("g",), 8)
        assert name not in B.PARTITION_KEYS   # no explicit None entry
        # a partitioned base registers its key, invalidation unregisters it
        sampling.invalidate(db2)
        B.PARTITION_KEYS["facts"] = "g"
        sampling.rung_database(db2, "facts", ("g",), 8)
        assert B.PARTITION_KEYS[name] == "g"
        planner.invalidate_stats(db2)
        assert name not in B.PARTITION_KEYS
    finally:
        B.PARTITION_KEYS.pop("facts", None)
        B.PARTITION_KEYS.pop(name, None)
        sampling.invalidate(db2)


# ---------------------------------------------------------------------------
# estimator unit behavior
# ---------------------------------------------------------------------------

def test_t_value_table_and_normal_limit():
    assert float(estimators.t_value(1)) == pytest.approx(12.706)
    assert float(estimators.t_value(10)) == pytest.approx(2.228)
    assert float(estimators.t_value(31)) == pytest.approx(
        estimators.z_value(0.95))
    df = np.array([1, 2, 5, 30, 100])
    t = estimators.t_value(df)
    assert np.all(np.diff(t) < 0)   # monotone toward the normal quantile


def test_z_value_bisection_fallback():
    # untabulated confidence: scipy-free erf inversion
    assert estimators.z_value(0.975) == pytest.approx(2.241402728, abs=1e-6)


def test_interval_honesty_gates():
    # m < 2: no variance estimate — infinite half-width
    _, hw = estimators.interval("sum", n=100, m=1, mf=1, s1=5.0, s2=25.0)
    assert np.isinf(hw)
    # fully sampled: exact — zero half-width
    _, hw = estimators.interval("sum", n=10, m=10, mf=4, s1=5.0, s2=25.0)
    assert float(hw) == 0.0
    # avg with a single post-filter row: infinite
    _, hw = estimators.interval("avg", n=100, m=8, mf=1, s1=5.0, s2=25.0)
    assert np.isinf(hw)


def test_non_estimable_ops_raise():
    with pytest.raises(ValueError):
        estimators.interval("min", 10, 5, 5, 1.0, 1.0)
    with pytest.raises(ValueError):
        estimators.point_estimate("max", 10, 5, 5, 1.0)


def test_finalize_raises_on_dropped_moments():
    """The tolerance guarantee's last line of defense: a scale-rewritten
    result whose __ap_* moments were projected away must raise, never be
    served as an exact zero-width answer."""
    with pytest.raises(ValueError, match="moment"):
        estimators.finalize_result({"s": np.array([7.0])},
                                   (("s", "sum"),), scaled=True)
    # scaled target present but its own s1/s2 moments missing
    with pytest.raises(ValueError, match="s1"):
        estimators.finalize_result(
            {"s": np.array([7.0]),
             estimators.N_COL: np.array([16]),
             estimators.M_COL: np.array([4]),
             estimators.MF_COL: np.array([4])},
            (("s", "sum"),), scaled=True)
    # unscaled (rung-1 / refused) results still pass through exact
    est = estimators.finalize_result({"s": np.array([7.0])},
                                     (("s", "sum"),), scaled=False)
    assert est.exact and est.rel_width == 0.0


# ---------------------------------------------------------------------------
# Monte-Carlo coverage: the statistical gate
# ---------------------------------------------------------------------------

def _scalar_coverage(op: str, den: int, trials: int, seed: int) -> float:
    """Empirical CI coverage for one op x rung on random skewed populations.

    Single global stratum, gamma(2, 10) values, a random filter at the
    0.2-0.6 quantile: the same moments the plan rewrite injects, computed
    directly so the gate isolates the estimator math.
    """
    rng = np.random.default_rng(seed)
    hits = 0
    for _ in range(trials):
        n = int(rng.integers(400, 2000))
        v = rng.gamma(2.0, 10.0, size=n)
        keep = v > np.quantile(v, rng.uniform(0.2, 0.6))
        mask, _, _, m_g = sampling.stratified_selection(
            [], n, den, seed=int(rng.integers(1 << 31)))
        m = int(m_g[0])
        sv, sk = v[mask], keep[mask]
        mf = int(sk.sum())
        if op == "avg":
            xs = sv[sk]
            s1, s2 = float(xs.sum()), float((xs * xs).sum())
            truth = float(v[keep].mean()) if keep.any() else np.nan
        else:
            x = np.where(sk, sv, 0.0)
            s1, s2 = float(x.sum()), float((x * x).sum())
            truth = float(v[keep].sum()) if op == "sum" else float(keep.sum())
        est, hw = estimators.interval(op, n, m, mf, s1, s2)
        if np.isinf(float(hw)) or (truth == truth and
                                   abs(truth - float(est)) <= float(hw)):
            hits += 1
    return hits / trials


@pytest.mark.parametrize("op", sorted(estimators.ESTIMABLE_OPS))
@pytest.mark.parametrize("den", DENS)
def test_coverage_smoke(op, den, approx_seed):
    """Tier-1 smoke: 20 trials per combo.  Gate 0.80 == nominal 0.95 minus
    3 sigma of binomial noise at 20 trials (deterministic at APPROX_SEED;
    the observed minimum across all combos is exactly 0.80)."""
    cov = _scalar_coverage(op, den, SMOKE_TRIALS, approx_seed + den)
    assert cov >= 0.80, f"{op} 1/{den}: coverage {cov}"


@pytest.mark.slow
@pytest.mark.parametrize("op", sorted(estimators.ESTIMABLE_OPS))
@pytest.mark.parametrize("den", DENS)
def test_coverage_full(op, den, approx_seed):
    """The ISSUE gate: >= 200 seeded trials, truth inside the 95 % CI at
    >= 90 % empirical rate for every estimable op x rung.  Observed rates at
    APPROX_SEED are 0.925-0.985."""
    cov = _scalar_coverage(op, den, FULL_TRIALS, approx_seed + den)
    assert cov >= 0.90, f"{op} 1/{den}: coverage {cov}"


def _group_coverage(op: str, den: int, trials: int, seed: int) -> float:
    """Group-level coverage through ``sample_table`` with 10 strata of
    wildly uneven sizes (4..400) — the small-m regime the t correction is
    for."""
    rng = np.random.default_rng(seed)
    hits = total = 0
    for _ in range(trials):
        sizes = rng.integers(4, 400, size=10)
        g = np.repeat(np.arange(10), sizes)
        v = rng.gamma(2.0, 10.0, size=g.size)
        samp = sampling.sample_table(
            {"g": g.astype(np.int64), "v": v}, ("g",), den,
            seed=int(rng.integers(1 << 31)))
        thr = np.quantile(v, 0.3)
        for gi in range(10):
            gm = samp["g"] == gi
            n, m = int(samp["__sn"][gm][0]), int(samp["__sm"][gm][0])
            sv = samp["v"][gm]
            sk = sv > thr
            mf = int(sk.sum())
            pop = v[g == gi]
            popk = pop > thr
            if op == "avg":
                xs = sv[sk]
                s1, s2 = float(xs.sum()), float((xs * xs).sum())
                truth = float(pop[popk].mean()) if popk.any() else np.nan
            else:
                x = np.where(sk, sv, 0.0)
                s1, s2 = float(x.sum()), float((x * x).sum())
                truth = (float(pop[popk].sum()) if op == "sum"
                         else float(popk.sum()))
            est, hw = estimators.interval(op, n, m, mf, s1, s2)
            total += 1
            if np.isinf(float(hw)) or (truth == truth and
                                       abs(truth - float(est)) <= float(hw)):
                hits += 1
    return hits / total


@pytest.mark.parametrize("op", sorted(estimators.ESTIMABLE_OPS))
@pytest.mark.parametrize("den", DENS)
def test_group_coverage(op, den, approx_seed):
    """200 group-observations (20 trials x 10 strata) per combo.  Gate 0.85:
    observations within a trial share one selection draw, so the effective
    sample is smaller than 200 — the observed minimum at APPROX_SEED is 0.88
    (count, 1/16); with the z-quantile instead of Student-t it was 0.843,
    which is what forced the t correction in ``estimators``."""
    cov = _group_coverage(op, den, SMOKE_TRIALS,
                          (approx_seed + den) ^ 0xABCDEF)
    assert cov >= 0.85, f"{op} 1/{den}: group coverage {cov}"


def test_plan_level_coverage_q1(db, approx_seed):
    """End-to-end: the rewritten q1 plan's per-group error bars cover the
    exact answers across 10 sampling seeds at rung 1/8 (>= 90 %)."""
    exact, _ = B.run_reference(QUERIES[1], db)
    keys = ("l_returnflag", "l_linestatus")
    exact_by_key = {tuple(int(exact[k][i]) for k in keys): i
                    for i in range(exact[keys[0]].size)}
    hits = total = 0
    for s in range(10):
        rw = rewrite_for_rung(QUERIES[1], db, 8, seed=approx_seed + s)
        cols, _ = B.run_reference(rw.query, rw.db)
        est = rw.finalize(cols)
        for name, _op in rw.targets:
            hw = est.half_width[name]
            for i in range(est.result[keys[0]].size):
                j = exact_by_key[tuple(int(est.result[k][i]) for k in keys)]
                total += 1
                if np.isinf(hw[i]) or \
                        abs(float(exact[name][j]) -
                            float(est.result[name][i])) <= float(hw[i]):
                    hits += 1
    assert total >= 10 * 4 * len(rw.targets) // 2
    assert hits / total >= 0.90, f"plan-level coverage {hits / total}"


# ---------------------------------------------------------------------------
# rung-1 differential identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wire", [None, "wide"])
@pytest.mark.parametrize("infer", [True, False])
@pytest.mark.parametrize("qid", [1, 6, 18])
def test_rung1_byte_identity(db, qid, infer, wire):
    """den == 1 is a pure scan rename: byte-identical to the exact plan on
    both planner legs (inference on/off == REPRO_PLANNER=1/0) and both wire
    formats."""
    rw = rewrite_for_rung(QUERIES[qid], db, 1)
    assert rw is not None and rw.den == 1
    exact, _ = B.run_local(QUERIES[qid].with_inference(infer), db,
                           jit=False, wire_format=wire)
    got, _ = B.run_local(rw.query.with_inference(infer), rw.db,
                         jit=False, wire_format=wire)
    assert set(exact) == set(got)
    for k in exact:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(exact[k]), err_msg=k)
    assert rw.finalize(got).exact


def test_rung1_byte_identity_jitted(db):
    """One jitted leg to pin the compiled path too."""
    rw = rewrite_for_rung(QUERIES[6], db, 1)
    exact, _ = B.run_local(QUERIES[6], db, jit=True, wire_format="wide")
    got, _ = B.run_local(rw.query, rw.db, jit=True, wire_format="wide")
    for k in exact:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(exact[k]), err_msg=k)


# ---------------------------------------------------------------------------
# refusal: the honest "run exact" answers
# ---------------------------------------------------------------------------

def _synth_db(rows=512, groups=8, seed=0):
    rng = np.random.default_rng(APPROX_SEED + seed)
    return Database(tables={"facts": {
        "g": rng.integers(0, groups, rows).astype(np.int64),
        "v": rng.normal(size=rows)}}, dicts={}, scale=1.0)


def test_refuses_min_max(db):
    db2 = _synth_db()
    q = planner.compile_query(lambda: scan("facts").group_by(
        ["g"], [("mx", "max", "v")], exchange="gather", final=True),
        name="minmax")
    assert rewrite_for_rung(q, db2, 4, tables=("facts",)) is None
    # TPC-H shapes with min at the site refuse too
    assert rewrite_for_rung(QUERIES[2], db, 4) is None


def test_refuses_semi_join_counts(db):
    """q4's count is semi-join-dependent: no per-stratum weight scales it."""
    assert rewrite_for_rung(QUERIES[4], db, 4) is None


def test_refuses_tiny_table():
    db2 = _synth_db(rows=100)
    q = planner.compile_query(lambda: scan("facts").group_by(
        ["g"], [("s", "sum", "v")], exchange="gather", final=True),
        name="tiny")
    assert rewrite_for_rung(q, db2, 4, tables=("facts",)) is None
    assert rewrite_for_rung(q, db2, 4, tables=("facts",),
                            min_rows=10) is not None


def test_refuses_group_estimate_feeding_computation(db):
    """A GroupBy site's scaled estimates may only reach the root through
    projections and Finalize.  q18's grouped sum feeds a HAVING-style filter
    and two joins — group membership decided by an un-barred estimate — so
    every sampled rung refuses (rung 1 stays a pure rename, tested above)."""
    for den in (16, 8, 4, 2):
        assert rewrite_for_rung(QUERIES[18], db, den) is None
    # synthetic minimal shape: Filter directly on the aggregate output
    db2 = _synth_db()
    q = planner.compile_query(lambda: scan("facts").group_by(
        ["g"], [("s", "sum", "v")], exchange="gather", final=True)
        .filter(col("s") > 0.0).finalize(replicated=True), name="having")
    assert rewrite_for_rung(q, db2, 4, tables=("facts",)) is None
    # SQL HAVING lowers to exactly that Filter
    from repro.sql import compile_sql
    qs = compile_sql("SELECT l_returnflag, sum(l_quantity) AS sq "
                     "FROM lineitem GROUP BY l_returnflag "
                     "HAVING sum(l_quantity) > 100", name="having_sql")
    assert rewrite_for_rung(qs, db, 4) is None


def test_select_above_site_keeps_moments(db):
    """SQL lowering emits a Select above the GroupBy whenever the SELECT
    list reorders or omits outputs (lower.py); the rewrite must extend that
    projection so the moment columns reach finalize — this was the silent
    width-0.0 bug: a den=16 HT estimate served as exact."""
    from repro.sql import compile_sql
    q = compile_sql("SELECT sum(l_quantity) AS sq, l_returnflag "
                    "FROM lineitem GROUP BY l_returnflag "
                    "ORDER BY l_returnflag", name="reorder")
    for den in (16, 4):
        rw = rewrite_for_rung(q, db, den)
        assert rw is not None
        cols, _ = B.run_reference(rw.query, rw.db)
        assert estimators.N_COL in cols        # moments survived the Select
        est = rw.finalize(cols)
        assert 0.0 < est.rel_width < np.inf    # honest bars, not fake-exact
        assert estimators.N_COL not in est.result
    # plan-level: the projection may also drop a target — it is then simply
    # not served, while the surviving target keeps its bars
    db2 = _synth_db(rows=2048)
    qp = planner.compile_query(lambda: scan("facts").group_by(
        ["g"], [("s", "sum", "v"), ("c", "count", None)],
        exchange="gather", final=True).select("s", "g")
        .finalize(sort_keys=[("g", True)], replicated=True), name="proj")
    rw = rewrite_for_rung(qp, db2, 4, tables=("facts",))
    cols, _ = B.run_reference(rw.query, rw.db)
    est = rw.finalize(cols)
    assert "c" not in est.result and "s" in est.half_width
    assert est.rel_width > 0.0


def test_refuses_estimate_in_scalar_arithmetic():
    """A scalar estimate folded into arithmetic has no attachable bar."""
    db2 = _synth_db()
    base = scan("facts")
    agg = base.agg_scalar([("s", "sum", "v"), ("c", "count", None)])
    q = planner.compile_query(
        lambda: P.ScalarResult({"ratio": P.ScalarRef(agg, "s") /
                                P.ScalarRef(agg, "c")}), name="ratio")
    assert rewrite_for_rung(q, db2, 4, tables=("facts",)) is None


def test_progressive_rejects_off_ladder_rung(db):
    """A custom ladder with a denominator the sampler has no rung for must
    fail at construction, not blow up mid-run()."""
    with pytest.raises(ValueError, match="sampling ladder"):
        progressive.ProgressiveRunner(db, ladder=(32, 16, 1))
    # valid subsets of the sampling ladder are still accepted
    r = progressive.ProgressiveRunner(db, ladder=(16, 4, 1))
    assert r.ladder == (16, 4, 1)


def test_progressive_exact_fallback(db):
    runner = progressive.ProgressiveRunner(db, tolerance=0.5,
                                           local_jit=False)
    ans = runner.run(QUERIES[4])
    assert ans.rung == 0 and ans.exact and ans.ci_width == 0.0
    exact, _ = B.run_reference(QUERIES[4], db)
    for k in exact:
        np.testing.assert_array_equal(np.asarray(ans.result[k]),
                                      np.asarray(exact[k]))
    assert ans.report.attempts[-1].rung == 0


# ---------------------------------------------------------------------------
# progressive escalation
# ---------------------------------------------------------------------------

def test_absent_group_escalates_never_fabricates(db):
    """Adversarial: one qualifying row per group.  Small rungs mostly miss
    it; any group they do emit must be a genuine (weighted) observation —
    never a fabricated zero — and the ladder must climb to the exact rung."""
    rng = np.random.default_rng(APPROX_SEED + 9)
    g = np.repeat(np.arange(8), 64).astype(np.int64)
    v = np.tile(np.arange(64), 8).astype(np.int64)
    perm = rng.permutation(g.size)             # scramble rows, keep pairing
    db2 = Database(tables={"facts": {"g": g[perm], "v": v[perm]}},
                   dicts={}, scale=1.0)

    def build():
        return scan("facts").filter(col("v") > 62).group_by(
            ["g"], [("c", "count", None), ("s", "sum", "v")],
            exchange="gather", final=True) \
            .finalize(sort_keys=[("g", True)], replicated=True)

    q = planner.compile_query(build, name="needle")
    # direct look at a small rung: groups may be absent, never zero
    rw = rewrite_for_rung(q, db2, 4, tables=("facts",))
    cols, _ = B.run_reference(rw.query, rw.db)
    assert cols["g"].size <= 8
    assert np.all(np.asarray(cols["c"], np.float64) > 0)
    assert np.all(np.asarray(cols["s"], np.float64) > 0)
    # the ladder ends at the exact full-table rung
    runner = progressive.ProgressiveRunner(db2, tolerance=0.05,
                                           tables=("facts",),
                                           local_jit=False)
    ans = runner.run(q)
    assert ans.rung == 1 and ans.exact
    np.testing.assert_array_equal(ans.result["g"], np.arange(8))
    np.testing.assert_array_equal(np.asarray(ans.result["c"], np.int64),
                                  np.ones(8, np.int64))
    np.testing.assert_array_equal(np.asarray(ans.result["s"], np.int64),
                                  np.full(8, 63))
    assert ans.escalations == len(ans.report.attempts) - 1


def test_progressive_termination_property(db):
    """Hypothesis property: for any tolerance the runner terminates with a
    final interval within tolerance or the exact top rung; every climb is an
    audited TOLERANCE_MISS whose measured width exceeded the tolerance.
    Falls back to a seeded log-uniform sweep when hypothesis is absent (the
    image does not ship it; the CI approx job runs the real property)."""
    def prop(tol):
        runner = progressive.ProgressiveRunner(db, tolerance=tol,
                                               local_jit=False)
        ans = runner.run(QUERIES[6])
        rungs = [a.rung for a in ans.report.attempts]
        assert rungs == sorted(rungs, reverse=True)   # climbs monotonically
        assert ans.rung >= 1                          # q6 is estimable
        assert ans.ci_width <= tol or ans.rung == 1
        for a in ans.report.attempts[:-1]:
            assert a.outcome == "tolerance_miss"
            assert a.ci_width > tol
        assert ans.report.attempts[-1].outcome == "ok"
        assert ans.escalations == len(ans.report.attempts) - 1

    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        rng = np.random.default_rng(APPROX_SEED)
        for tol in 10.0 ** rng.uniform(-4.0, 1.0, size=6):
            prop(float(tol))
        return
    settings(max_examples=8, deadline=None, derandomize=True)(
        given(tol=st.floats(min_value=1e-4, max_value=10.0,
                            allow_nan=False, allow_infinity=False))(prop))()


def test_progressive_rung1_is_exact(db):
    """tolerance=0 forces the whole ladder; the top rung answers exactly:
    bit for bit what the exact plan answers.  The NumPy reference adds the
    revenue, a float64 sum, in row order, where the engine reduces it
    masked (a tree), so the two agree to 1e-12 relative."""
    runner = progressive.ProgressiveRunner(db, tolerance=0.0,
                                           local_jit=False)
    ans = runner.run(QUERIES[6])
    assert ans.rung == 1 and ans.exact and ans.ci_width == 0.0
    own, stats = B.run_local(QUERIES[6], db, jit=False)
    assert set(ans.result) == set(own) == {"revenue"}
    np.testing.assert_array_equal(np.asarray(ans.result["revenue"]),
                                  np.asarray(own["revenue"]))
    assert stats.agg_reductions.get("masked")
    exact, _ = B.run_reference(QUERIES[6], db)
    np.testing.assert_allclose(np.asarray(ans.result["revenue"]),
                               np.asarray(exact["revenue"]), rtol=1e-12,
                               atol=0)
    assert [a.rung for a in ans.report.attempts] == [16, 8, 4, 2, 1]


# ---------------------------------------------------------------------------
# surfacing: audit table + serving
# ---------------------------------------------------------------------------

def test_run_report_renders_rung_and_ci(db, capsys):
    from repro.launch import report as rep
    runner = progressive.ProgressiveRunner(db, tolerance=0.0,
                                           local_jit=False)
    ans = runner.run(QUERIES[6])
    rec = rep.run_report_record("q6", ans.report)
    rec = json.loads(json.dumps(rec))          # must stay JSON-able
    fallback = progressive.ProgressiveRunner(db, tolerance=0.5,
                                             local_jit=False).run(QUERIES[4])
    rec2 = json.loads(json.dumps(rep.run_report_record("q4",
                                                       fallback.report)))
    rep.run_report_table([rec, rec2])
    out = capsys.readouterr().out
    assert "| rung | ci |" in out
    for den in (16, 8, 4, 2):
        assert f"| 1/{den} |" in out
    assert "| 1/1 | 0.00% |" in out            # the exact top rung
    assert "| exact |" in out                  # q4's rung-0 fallback
    # climbed rungs are tolerance_miss rows with a percentage ci cell
    miss = [ln for ln in out.splitlines() if "tolerance_miss" in ln]
    assert len(miss) == 4 and all("%" in ln for ln in miss)


def test_serve_tolerance_path(db):
    from repro import serve
    srv = serve.QueryServer(db)
    r = srv.submit(6, tolerance=0.5)
    assert srv.approx_served == 1 and srv.approx_escalations == 0
    assert r["revenue"].size == 1
    rc0, h0 = srv.recompiles, srv.cache_hits
    srv.submit(6, tolerance=0.5)               # rewrite + executable cached
    assert srv.recompiles == rc0 and srv.cache_hits >= h0 + 2
    # tolerance=0 climbs the whole ladder; rung 1 == exact, byte for byte
    approx = srv.submit(6, tolerance=0.0)
    exact = srv.submit(6)
    assert set(approx) == set(exact)
    for k in exact:
        np.testing.assert_array_equal(approx[k], exact[k])
    assert srv.approx_escalations == 4
    # a refused shape serves exact and says so
    r4 = srv.submit(4, tolerance=0.5)
    assert srv.approx_refused == 1
    exact4, _ = B.run_reference(QUERIES[4], db)
    np.testing.assert_array_equal(np.asarray(r4["order_count"]),
                                  np.asarray(exact4["order_count"]))


def test_approx_default_env(monkeypatch):
    monkeypatch.delenv("REPRO_APPROX", raising=False)
    assert progressive.approx_default() is None
    monkeypatch.setenv("REPRO_APPROX", "off")
    assert progressive.approx_default() is None
    monkeypatch.setenv("REPRO_APPROX", "0.25")
    assert progressive.approx_default() == 0.25


def test_serve_env_default_tolerance(db, monkeypatch):
    from repro import serve
    monkeypatch.setenv("REPRO_APPROX", "0.5")
    srv = serve.QueryServer(db)
    srv.submit(6)                              # no tolerance= needed
    assert srv.approx_served == 1
