"""Planner differential suite: inferred hints vs the deleted hand hints,
hinted-vs-unhinted byte identity, exchange-placement validation, and the
hash-join bucket overflow -> ctx.overflow -> capacity-escalation wiring.
"""
import numpy as np
import pytest

from repro.core import backend as B
from repro.core import plan as P
from repro.core import planner as PL
from repro.data import tpch
from repro.queries import QUERIES


@pytest.fixture(scope="module")
def db():
    return tpch.generate(0.005, seed=11)


# ---------------------------------------------------------------------------
# inferred hints at least as tight as the deleted hand hints
# ---------------------------------------------------------------------------

# The hand-threaded hints PR 2 carried on the final group-by of each of these
# plans, deleted in this PR: {qid: (groups_hint, sum(key_bits))}.  The planner
# must prove bounds no looser than what the hand plans claimed.
_HAND_HINTS = {
    1: (8, 3),     # dict_bits(l_returnflag)+dict_bits(l_linestatus)
    4: (8, 3),     # dict_bits(o_orderpriority)
    5: (32, 5),    # nationkey < 25
    7: (16, 13),   # grp < 25*25*8
    8: (16, 11),   # o_year from the 1970-2005 LUT
    9: (512, 9),   # grp = nationkey*16 + (year-1992) < 400
    12: (16, 3),   # dict_bits(l_shipmode)
    22: (40, 6),   # c_phone_cc = nationkey + 10 < 35
}


def _final_group_by(qid):
    gbs = [n for n in PL.walk(QUERIES[qid].plan)
           if isinstance(n, P.GroupBy) and n.final]
    assert len(gbs) == 1, qid
    return gbs[0]


@pytest.mark.parametrize("qid", sorted(_HAND_HINTS))
def test_inferred_hints_at_least_as_tight_as_hand_hints(db, qid):
    hand_gh, hand_bits = _HAND_HINTS[qid]
    kb, gh = QUERIES[qid].info(db).hints_for(_final_group_by(qid))
    assert kb is not None, f"q{qid}: planner failed to prove key_bits"
    assert sum(kb) <= hand_bits, \
        f"q{qid}: inferred bits {kb} looser than hand {hand_bits}"
    assert gh is not None, f"q{qid}: planner failed to prove groups_hint"
    assert gh <= hand_gh, \
        f"q{qid}: inferred groups_hint {gh} looser than hand {hand_gh}"


@pytest.mark.parametrize("qid", sorted(_HAND_HINTS))
def test_inferred_bits_unlock_direct_path(db, qid):
    """Every previously-hinted plan still takes the sortless direct path."""
    from repro.core.relational import DIRECT_AGG_BITS_MAX
    kb, _ = QUERIES[qid].info(db).hints_for(_final_group_by(qid))
    assert kb is not None and sum(kb) <= DIRECT_AGG_BITS_MAX


def test_no_hand_key_bits_left_in_query_code():
    """The builder has no key_bits parameter, so plans cannot state widths;
    double-check no plan smuggles one through groups_hint-less GroupBy."""
    import inspect
    from repro import queries
    for mod in (queries.q01_08, queries.q09_15, queries.q16_22):
        assert "key_bits=" not in inspect.getsource(mod)


# ---------------------------------------------------------------------------
# hinted (inference on) == unhinted (inference off), byte for byte
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qid", sorted(QUERIES))
def test_inference_on_off_byte_identical(db, qid):
    """The compiled hinted path and the conservative unhinted path must agree
    bit for bit on the local backend — the planner cannot silently diverge
    from the legacy eager semantics.  Hinted joins on a proven dense key
    take the direct-address index; unhinted ones keep the sorted index.

    Byte identity holds per aggregation engine: under REPRO_AGG_KERNEL=1 the
    hinted direct path sums on the (interpret-mode) MXU one-hot kernel while
    the unhinted path uses segment_sum, so that leg compares at the same
    rtol=1e-9 the kernel-vs-oracle suite (test_aggregate_paths) pins."""
    from repro.core.relational import agg_kernel_default
    r_on, s_on = B.run_local(QUERIES[qid].with_inference(True), db)
    r_off, s_off = B.run_local(QUERIES[qid].with_inference(False), db)
    assert set(r_on) == set(r_off)
    for k in r_on:
        if agg_kernel_default():
            np.testing.assert_allclose(
                np.asarray(r_on[k], np.float64),
                np.asarray(r_off[k], np.float64),
                rtol=1e-9, err_msg=f"q{qid} {k}")
        else:
            np.testing.assert_array_equal(r_on[k], r_off[k],
                                          err_msg=f"q{qid} {k}")
    assert s_on.counts() == s_off.counts()   # hints never move exchanges
    # inference off builds every join index sorted, as before the planner
    # proved key ranges; inference on builds the same number of indexes
    assert set(s_off.index_builds) <= {"sorted"}
    assert sum(s_on.index_builds.values()) == \
        sum(s_off.index_builds.values())


# Join indexes each benchmarked query builds with inference on, by method:
# every single-column join on the direct path; Q9 (a control, not
# benchmarked) keeps its two-column partsupp join on the sorted index.
_INDEX_BUILDS = {1: {}, 3: {"direct": 2}, 5: {"direct": 5}, 6: {},
                 9: {"direct": 3, "sorted": 1}, 10: {"direct": 2},
                 12: {"direct": 1}, 14: {"direct": 1}, 18: {"direct": 2},
                 19: {"direct": 1}}


@pytest.mark.parametrize("qid", sorted(_INDEX_BUILDS))
def test_join_index_methods(db, qid):
    """The engagement counter: which index each join of a plan builds, as
    the trace counts it (nothing runs)."""
    import jax
    tables = B._np_db_to_tables(db)

    def builds(infer):
        ctx = {}

        def run(tables):
            ctx["c"] = B.LocalContext(db, tables)
            return QUERIES[qid].run(ctx["c"], infer=infer)

        jax.eval_shape(run, tables)
        return ctx["c"].stats.index_builds

    assert builds(True) == _INDEX_BUILDS[qid]
    n = sum(_INDEX_BUILDS[qid].values())
    assert builds(False) == ({"sorted": n} if n else {})
    ranges = [QUERIES[qid].info(db).join_range_for(n)
              for n in PL.walk(QUERIES[qid].plan)
              if isinstance(n, P._JoinBase)]
    assert sum(r is None for r in ranges) == \
        _INDEX_BUILDS[qid].get("sorted", 0)


@pytest.mark.parametrize("qid", sorted(QUERIES))
def test_builder_plans_match_reference(db, qid):
    """All 22 builder plans match the NumPy oracle (local backend; the
    distributed leg lives in test_distributed.py)."""
    r_ref, _ = B.run_reference(QUERIES[qid], db)
    r_loc, _ = B.run_local(QUERIES[qid].with_inference(True), db)
    n = len(next(iter(r_ref.values())))
    for k in set(r_ref) & set(r_loc):
        assert len(r_loc[k]) == n
        np.testing.assert_allclose(np.asarray(r_loc[k], np.float64),
                                   np.asarray(r_ref[k], np.float64),
                                   rtol=1e-7, err_msg=f"q{qid} {k}")


# ---------------------------------------------------------------------------
# exchange-placement validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qid", sorted(QUERIES))
def test_paper_placement_validates_clean(db, qid):
    """The derived placement agrees with the paper's explicit placement on
    all 22 plans (Q11's deviation is a count difference vs the paper's table,
    not a placement inconsistency)."""
    assert QUERIES[qid].validate(db) == []


def test_validation_flags_redundant_exchange(db):
    # lineitem is partitioned by l_orderkey: a shuffle to it is removable,
    # and a grouped shuffle over a co-partitioned key likewise
    plan = P.scan("lineitem").shuffle("l_orderkey").finalize()
    notes = PL.validate(plan, db)
    assert any("removable" in n for n in notes), notes
    plan2 = P.scan("lineitem").group_by(
        ["l_orderkey"], [("n", "count", None)],
        exchange="shuffle").finalize()
    notes2 = PL.validate(plan2, db)
    assert any("removable" in n for n in notes2), notes2


def test_validation_flags_non_disjoint_local_group(db):
    # grouping lineitem by suppkey locally while partitioned by orderkey
    # produces per-device partials consumed as a global result -> flagged
    plan = P.scan("lineitem").group_by(
        ["l_suppkey"], [("n", "count", None)], exchange="local").finalize()
    notes = PL.validate(plan, db)
    assert any("span devices" in n for n in notes), notes


def test_validation_flags_missing_join_exchange(db):
    # joining two tables partitioned on unrelated keys without an exchange
    plan = P.scan("lineitem").join(P.scan("customer"), "l_suppkey",
                                   "c_custkey", []).finalize()
    notes = PL.validate(plan, db)
    assert any("not co-partitioned" in n for n in notes), notes


def test_validation_accepts_membership_only_partial_group(db):
    # the Q20 idiom: a partial local group-by consumed only through
    # broadcast -> semi (key membership) is globally exact -> no flag
    sk = P.scan("lineitem").group_by(["l_suppkey"], [("n", "count", None)],
                                     exchange="local")
    skb = sk.select("l_suppkey").broadcast()
    s = P.scan("supplier").semi(skb, "s_suppkey", "l_suppkey")
    assert PL.validate(s.finalize(), db) == []


def test_static_counts_need_no_database():
    """Table-4 derivation is pure IR analysis."""
    plan = P.scan("lineitem").select("l_orderkey").broadcast().finalize()
    assert PL.static_plan_stats(plan) == {
        "shuffles": 0, "broadcasts": 1, "final_gathers": 1, "allreduces": 0}


# ---------------------------------------------------------------------------
# bound propagation unit checks
# ---------------------------------------------------------------------------

def test_filter_refinement_bounds_year_expression(db):
    info = QUERIES[7].info(db)
    kb, gh = info.hints_for(_final_group_by(7))
    # s/c_nationkey filtered to {FRANCE, GERMANY} and l_year to 1995-1996:
    # the packed grp domain collapses to at most 2*2*2 = 8 groups
    assert gh <= 8
    assert sum(kb) <= 11


def test_pinned_query_keeps_planner_surface(db):
    """with_inference() pins the mode but must keep the CompiledQuery surface
    (the fault runner's hint-drop recovery re-pins via with_inference)."""
    p = QUERIES[13].with_inference(True)
    assert p.static_counts() == QUERIES[13].static_counts()
    q = p.with_inference(False)
    r_on, _ = B.run_local(p, db)
    r_off, _ = B.run_local(q, db)
    for k in r_on:
        np.testing.assert_allclose(np.asarray(r_on[k], np.float64),
                                   np.asarray(r_off[k], np.float64),
                                   rtol=1e-9)


def test_stats_override_is_scoped(db):
    """planner.stats_override must restore actual-scale stats and drop every
    dependent PlanInfo on both entry and exit (the SF=1000 dry-run contract)."""
    from repro.core.planner import ColStats, column_stats, stats_override
    pre = column_stats(db)["o_custkey"]
    QUERIES[10].info(db)                      # warm a dependent PlanInfo
    with stats_override(db, {**column_stats(db),
                             "o_custkey": ColStats(1, 1 << 27, 1 << 27)}):
        assert column_stats(db)["o_custkey"].hi == 1 << 27
        gb = [n for n in PL.walk(QUERIES[10].plan)
              if isinstance(n, P.GroupBy)][0]
        kb, _ = QUERIES[10].info(db).hints_for(gb)
        assert kb is None                     # 28 bits: no direct path
    assert column_stats(db)["o_custkey"] == pre
    gb = [n for n in PL.walk(QUERIES[10].plan) if isinstance(n, P.GroupBy)][0]
    kb, _ = QUERIES[10].info(db).hints_for(gb)
    assert kb is not None                     # re-inferred at actual scale


def test_isin_rejects_empty_set_at_build_time():
    with pytest.raises(ValueError, match="empty value set"):
        P.isin(P.col("x"), [])


def test_expr_has_no_truth_value():
    """`a <= x < b` or `p and q` would silently drop a conjunct via implicit
    bool(); the builder must refuse instead of compiling a wrong predicate."""
    with pytest.raises(TypeError, match="truth value"):
        bool(P.col("l_shipdate") <= 42)
    with pytest.raises(TypeError, match="truth value"):
        (P.col("a") > 0) and (P.col("b") > 0)          # noqa: B015
    with pytest.raises(TypeError):
        1 <= P.col("l_shipdate") < 9999                # chained comparison


def test_explain_renders(db):
    text = QUERIES[1].explain(db)
    assert "group_by['l_returnflag', 'l_linestatus']" in text
    assert "direct (sortless)" in text


# ---------------------------------------------------------------------------
# hash-join bucket overflow -> ctx.overflow -> capacity escalation
# ---------------------------------------------------------------------------

def test_hash_bucket_overflow_sets_ctx_overflow(db):
    """A starved capacity factor overflows the hash-join bucket table; the
    flag must surface on ctx.overflow (run_local asserts on it) instead of
    failing locally inside kernels/hash_probe, and the fault-runner-style
    escalation loop must clear it and reproduce the oracle's answer."""
    with pytest.raises(AssertionError, match="overflow"):
        B.run_local(QUERIES[9], db, join_method="hash", capacity_factor=0.25)

    factor, result = 0.25, None
    for _ in range(6):                       # QueryRunner's discipline
        try:
            result, _ = B.run_local(QUERIES[9], db, join_method="hash",
                                    capacity_factor=factor)
            break
        except AssertionError:
            factor *= 2.0
    assert result is not None and factor > 0.25
    r_ref, _ = B.run_reference(QUERIES[9], db)
    np.testing.assert_allclose(np.asarray(result["sum_profit"], np.float64),
                               np.asarray(r_ref["sum_profit"], np.float64),
                               rtol=1e-7)


def test_bucket_cap_scales_with_capacity_factor(db):
    tables = B._np_db_to_tables(db)
    assert B.LocalContext(db, tables).bucket_cap() == 16      # historic cap
    assert B.LocalContext(db, tables,
                          capacity_factor=0.25).bucket_cap() == 2
    assert B.LocalContext(db, tables,
                          capacity_factor=8.0).bucket_cap() == 64
