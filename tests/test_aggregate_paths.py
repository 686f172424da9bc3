"""Differential tests: sortless (direct-addressing) vs sorted group_aggregate.

The phase-2 sort-tax work routes small-domain group-bys through dense group
ids + the ``kernels/segsum`` one-hot MXU reduce instead of an argsort, and
ranks shuffle rows with a radix-histogram counting rank instead of a stable
sort.  These tests pin the two paths together: same groups, same order, same
values (exact for int/count/min/max, 1e-12 for float sums), across masked and
compacted inputs, empty/all-invalid tables, single groups, and wrong hints
(which must flag overflow, never silently drop groups).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import backend as B
from repro.core import exchange as EX
from repro.core import relational as R
from repro.core.table import from_numpy, to_numpy
from repro.data import tpch
from repro.kernels.segsum import ops as ss
from repro.kernels.segsum.ref import segment_reduce_ref

OPS4 = [("s", "sum", "v"), ("c", "count", None),
        ("mn", "min", "v"), ("mx", "max", "v")]


def _random_table(seed, n=211, cap=256, kmax=16, k2max=8):
    rng = np.random.default_rng(seed)
    return from_numpy({
        "k": rng.integers(0, kmax, n).astype(np.int64),
        "k2": rng.integers(0, k2max, n).astype(np.int64),
        "v": rng.normal(size=n),
        "i": rng.integers(-50, 50, n).astype(np.int64),
    }, capacity=cap)


def _assert_tables_equal(got, want, float_cols=("s",)):
    gd, wd = to_numpy(got), to_numpy(want)
    assert set(gd) == set(wd)
    assert int(got.count) == int(want.count)
    for k in wd:
        if k in float_cols or wd[k].dtype.kind == "f":
            np.testing.assert_allclose(gd[k], wd[k], rtol=1e-12, atol=1e-12,
                                       err_msg=k)
        else:
            np.testing.assert_array_equal(gd[k], wd[k], err_msg=k)


@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("masked", [True, False])
def test_direct_matches_sorted_all_ops(use_kernel, masked):
    t = _random_table(0)
    if masked:
        t = R.filter_rows(t, t["v"] > -0.4)   # leaves a validity mask
    aggs = OPS4 + [("imn", "min", "i"), ("imx", "max", "i"),
                   ("isum", "sum", "i")]
    direct = R.group_aggregate(t, ["k", "k2"], aggs, key_bits=[4, 3],
                               method="direct", use_kernel=use_kernel)
    sortd = R.group_aggregate(t, ["k", "k2"], aggs, method="sort")
    _assert_tables_equal(direct, sortd)


@pytest.mark.parametrize("use_kernel", [True, False])
def test_direct_empty_and_all_invalid(use_kernel):
    t = _random_table(1, n=0, cap=32)
    for tt in (t, R.filter_rows(_random_table(2), _random_table(2)["v"] > 99)):
        direct = R.group_aggregate(tt, ["k"], OPS4, key_bits=[4],
                                   method="direct", use_kernel=use_kernel)
        sortd = R.group_aggregate(tt, ["k"], OPS4, method="sort")
        assert int(direct.count) == int(sortd.count) == 0
        _assert_tables_equal(direct, sortd)


def test_direct_single_group():
    t = _random_table(3)
    t = t.replace(k=jnp.zeros_like(t["k"]) + 5)
    direct = R.group_aggregate(t, ["k"], OPS4, key_bits=[4], method="direct")
    sortd = R.group_aggregate(t, ["k"], OPS4, method="sort")
    assert int(direct.count) == 1
    _assert_tables_equal(direct, sortd)


def test_scalar_agg_direct_matches_sorted():
    t = R.filter_rows(_random_table(4), _random_table(4)["v"] < 0.9)
    direct = R.group_aggregate(t, [], OPS4, method="direct")
    sortd = R.group_aggregate(t, [], OPS4, method="sort")
    _assert_tables_equal(direct, sortd)


def test_lying_key_bits_flags_overflow_never_corrupts():
    """key_bits smaller than the true domain: out-of-domain groups go to the
    dead slot and the overflow flag fires — in-domain groups stay exact."""
    t = _random_table(5, kmax=16)
    direct, ov = R.group_aggregate(t, ["k"], OPS4, key_bits=[3],
                                   method="direct", return_overflow=True)
    assert bool(ov)
    # the in-domain groups (k < 8) must still match the sorted path exactly
    t8 = R.filter_rows(t, t["k"] < 8)
    sortd = R.group_aggregate(t8, ["k"], OPS4, method="sort")
    _assert_tables_equal(direct, sortd)
    # honest bits: no overflow
    _, ov2 = R.group_aggregate(t, ["k"], OPS4, key_bits=[4],
                               method="direct", return_overflow=True)
    assert not bool(ov2)


def test_lying_bits_on_non_leading_column_flags_overflow():
    """An oversized value in a NON-leading key column ORs into its neighbor's
    bits and aliases an in-range packed key — the per-column domain check
    must still flag it (regression: a packed-key range check alone misses
    this corruption)."""
    n = 32
    cols = {"k": np.full(n, 1, np.int64), "k2": np.full(n, 5, np.int64),
            "v": np.ones(n)}
    t = from_numpy(cols, capacity=n)
    # claim k2 < 4 (false: k2 == 5); packed key (1<<2)|5 = 9 < 2^4 aliases
    # the honest group (k=2, k2=1)
    direct, ov = R.group_aggregate(t, ["k", "k2"], [("s", "sum", "v")],
                                   key_bits=[2, 2], method="direct",
                                   return_overflow=True)
    assert bool(ov)
    assert int(direct.count) == 0      # every row is out of claimed domain


def test_key_bits_larger_than_true_groups():
    """A generous domain claim shrinks correctly — no phantom groups."""
    t = _random_table(6, kmax=5)
    direct = R.group_aggregate(t, ["k"], OPS4, key_bits=[10], method="direct")
    sortd = R.group_aggregate(t, ["k"], OPS4, method="sort")
    _assert_tables_equal(direct, sortd)


def test_auto_dispatch_and_forced_direct_raises():
    t = _random_table(7)
    # auto: bits present and small -> direct == sort
    auto = R.group_aggregate(t, ["k"], OPS4, key_bits=[4])
    sortd = R.group_aggregate(t, ["k"], OPS4, method="sort")
    _assert_tables_equal(auto, sortd)
    with pytest.raises(ValueError):
        R.group_aggregate(t, ["k"], OPS4, method="direct")  # no bits
    with pytest.raises(ValueError):
        R.group_aggregate(t, ["k"], OPS4, key_bits=[20], method="direct")


def test_groups_hint_smaller_and_larger_than_true_groups():
    """Backend-level: hint < true groups flags ctx.overflow (re-execution),
    hint >= true groups returns the exact result — never a silent drop."""
    db = tpch.generate(0.002, seed=3)
    tables = B._np_db_to_tables(db)
    o = tables["orders"]

    def run(hint):
        ctx = B.LocalContext(db, tables)
        g = ctx.group_by(o, ["o_orderpriority"],
                         [("n", "count", None)], groups_hint=hint,
                         key_bits=[ctx.dict_bits("o_orderpriority")])
        return g, bool(ctx.overflow)

    big, ov_big = run(8)
    assert not ov_big and int(big.count) == 5
    small, ov_small = run(2)
    assert ov_small          # 5 priorities cannot fit 2 slots -> re-execute
    assert int(np.asarray(small.count)) == 2  # shrunk, flagged, not silent


# ---------------------------------------------------------------------------
# hash-compaction path (data-dependent domains): hash == sort, byte for byte
# ---------------------------------------------------------------------------

def _wide_key_table(seed, n=211, cap=256, masked=False):
    """Keys from a WIDE, data-dependent domain (negatives included) — exactly
    what the direct path cannot take and the hash dictionary exists for."""
    rng = np.random.default_rng(seed)
    t = from_numpy({
        "k": rng.integers(-1000, 1 << 40, n).astype(np.int64),
        "v": rng.normal(size=n),
        "i": rng.integers(-50, 50, n).astype(np.int64),
    }, capacity=cap)
    if masked:
        t = R.filter_rows(t, t["v"] > -0.4)
    return t


@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("masked", [True, False])
def test_hash_matches_sorted_all_ops(use_kernel, masked):
    t = _wide_key_table(20, masked=masked)
    aggs = OPS4 + [("imn", "min", "i"), ("imx", "max", "i"),
                   ("isum", "sum", "i")]
    hashed = R.group_aggregate(t, ["k"], aggs, method="hash",
                               groups_hint=256, use_kernel=use_kernel)
    sortd = R.group_aggregate(t, ["k"], aggs, method="sort")
    _assert_tables_equal(hashed, sortd)


@pytest.mark.parametrize("use_kernel", [True, False])
def test_hash_two_col_keys_match_sorted(use_kernel):
    rng = np.random.default_rng(21)
    n = 180
    t = from_numpy({
        "a": rng.integers(0, 1 << 20, n).astype(np.int64),
        "b": rng.integers(0, 7, n).astype(np.int64),
        "v": rng.normal(size=n),
    }, capacity=256)
    hashed = R.group_aggregate(t, ["a", "b"], OPS4, method="hash",
                               groups_hint=512, use_kernel=use_kernel)
    sortd = R.group_aggregate(t, ["a", "b"], OPS4, method="sort")
    _assert_tables_equal(hashed, sortd)


@pytest.mark.parametrize("use_kernel", [True, False])
def test_hash_empty_and_all_invalid(use_kernel):
    t = _random_table(22, n=0, cap=32)
    allinv = R.filter_rows(_wide_key_table(23), _wide_key_table(23)["v"] > 99)
    for tt in (t, allinv):
        hashed = R.group_aggregate(tt, ["k"], OPS4, method="hash",
                                   groups_hint=64, use_kernel=use_kernel)
        sortd = R.group_aggregate(tt, ["k"], OPS4, method="sort")
        assert int(hashed.count) == int(sortd.count) == 0


def test_hash_undercounting_hint_flags_overflow():
    """groups_hint below the true distinct count must flag overflow; the
    headroom factor may still have placed every group, in which case the
    output is complete AND flagged (re-execution discipline, never silent)."""
    t = _wide_key_table(24)                      # ~200 distinct keys
    hashed, ov = R.group_aggregate(t, ["k"], OPS4, method="hash",
                                   groups_hint=32, hash_factor=16.0,
                                   return_overflow=True)
    assert bool(ov)
    sortd = R.group_aggregate(t, ["k"], OPS4, method="sort")
    _assert_tables_equal(hashed, sortd)          # dict held them all anyway
    # honest hint: no overflow
    _, ov2 = R.group_aggregate(t, ["k"], OPS4, method="hash",
                               groups_hint=256, return_overflow=True)
    assert not bool(ov2)


@pytest.mark.parametrize("use_kernel", [True, False])
def test_hash_dict_overflow_escalation_clears(use_kernel):
    """A starved capacity factor leaves rows unplaceable (dictionary smaller
    than the distinct groups) -> overflow; doubling the factor — exactly what
    the fault runner's escalation does — clears it and reproduces the sort
    path.  Unplaced rows are EXCLUDED, never misassigned: every group the
    flagged run does emit is exact."""
    rng = np.random.default_rng(25)
    n = 230
    t = from_numpy({"k": rng.integers(0, 1 << 35, n).astype(np.int64),
                    "v": rng.normal(size=n)}, capacity=256)
    aggs = [("s", "sum", "v"), ("c", "count", None)]
    factor = 0.125                               # dict cap 32 < ~200 distinct
    hashed, ov = R.group_aggregate(t, ["k"], aggs, method="hash",
                                   groups_hint=230, hash_factor=factor,
                                   use_kernel=use_kernel,
                                   return_overflow=True)
    assert bool(ov)
    sortd = to_numpy(R.group_aggregate(t, ["k"], aggs, method="sort"))
    got = to_numpy(hashed)
    want = {int(k): (s, c) for k, s, c in
            zip(sortd["k"], sortd["s"], sortd["c"])}
    for k, s, c in zip(got["k"], got["s"], got["c"]):
        ws, wc = want[int(k)]
        assert wc == c and abs(ws - s) < 1e-12   # emitted groups are exact
    while bool(ov):                              # QueryRunner's discipline
        factor *= 2.0
        hashed, ov = R.group_aggregate(t, ["k"], aggs, method="hash",
                                       groups_hint=230, hash_factor=factor,
                                       use_kernel=use_kernel,
                                       return_overflow=True)
        assert factor <= 16.0, "escalation failed to clear dict overflow"
    _assert_tables_equal(hashed,
                         R.group_aggregate(t, ["k"], aggs, method="sort"))


def test_hash_auto_dispatch_and_guards():
    t = _wide_key_table(26)
    # auto: no key_bits but a hint -> hash == sort
    auto = R.group_aggregate(t, ["k"], OPS4, groups_hint=256)
    sortd = R.group_aggregate(t, ["k"], OPS4, method="sort")
    _assert_tables_equal(auto, sortd)
    with pytest.raises(ValueError):
        R.group_aggregate(t, ["k"], OPS4, method="hash")      # no hint
    with pytest.raises(ValueError):
        R.group_aggregate(t, ["k"], OPS4, method="hash",
                          groups_hint=R.HASH_AGG_GROUPS_MAX + 1)
    # direct outranks hash when both are eligible (cheaper: no dictionary)
    t2 = _random_table(27)
    both = R.group_aggregate(t2, ["k"], OPS4, key_bits=[4], groups_hint=16)
    _assert_tables_equal(both, R.group_aggregate(t2, ["k"], OPS4,
                                                 method="sort"))


@pytest.mark.parametrize("use_kernel", [True, False])
def test_q13_hash_path_matches_sort_path_both_planner_legs(use_kernel):
    """The tentpole acceptance case: Q13's data-dependent c_count histogram.
    Inference ON compiles the hash path (planner rule), inference OFF the
    single-sort path — byte-identical results per engine, and both match the
    NumPy reference."""
    from repro.queries import QUERIES
    db = tpch.generate(0.002, seed=3)
    r_hash, _ = B.run_local(QUERIES[13].with_inference(True), db,
                            use_kernel=use_kernel)
    r_sort, _ = B.run_local(QUERIES[13].with_inference(False), db,
                            use_kernel=use_kernel)
    assert set(r_hash) == set(r_sort)
    for k in r_hash:
        np.testing.assert_array_equal(r_hash[k], r_sort[k], err_msg=k)
    r_ref, _ = B.run_reference(QUERIES[13], db)
    for k in set(r_ref) & set(r_hash):
        np.testing.assert_allclose(np.asarray(r_hash[k], np.float64),
                                   np.asarray(r_ref[k], np.float64),
                                   rtol=1e-7, err_msg=k)


# ---------------------------------------------------------------------------
# shuffle dispatch: counting rank == stable-sort rank, byte for byte
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("seed,n,parts", [(0, 1000, 8), (1, 77, 3),
                                          (2, 4096, 16), (3, 8, 1)])
def test_dispatch_offsets_match_stable_sort(seed, n, parts, use_kernel):
    rng = np.random.default_rng(seed)
    # include the drop bucket `parts` (padding rows), as shuffle produces
    dest = rng.integers(0, parts + 1, n).astype(np.int32)
    slot, counts = EX._dispatch_offsets(jnp.asarray(dest), parts,
                                        use_kernel=use_kernel)
    # oracle: stable sort on destination, position within the group
    order = np.argsort(dest, kind="stable")
    want = np.empty(n, np.int64)
    start = {}
    for i in order:
        want[i] = start.get(dest[i], 0)
        start[dest[i]] = want[i] + 1
    np.testing.assert_array_equal(np.asarray(slot), want)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.bincount(dest, minlength=parts + 1)[:parts])


# ---------------------------------------------------------------------------
# segsum dead-slot routing at lane boundaries (regression)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("groups", [127, 128, 129])
def test_segsum_dead_slot_lane_boundary(groups):
    """The dead slot is ALWAYS index ``groups``: caller sentinels (gid ==
    groups) and out-of-range ids must never alias a real group, even when
    groups+1 sits exactly on a 128-lane tile boundary (groups = 127)."""
    rng = np.random.default_rng(groups)
    n = 500
    gids = rng.integers(0, groups + 1, n).astype(np.int32)   # incl. sentinel
    gids[:4] = [groups, groups - 1, -3, groups + 7]          # edge ids
    vals = rng.normal(size=n).astype(np.float32)
    want = np.zeros(groups, np.float64)
    for g, v in zip(gids, vals):
        if 0 <= g < groups:
            want[g] += v
    for use_kernel in (True, False):
        got = ss.segment_reduce(jnp.asarray(gids), jnp.asarray(vals), groups,
                                op="sum", use_kernel=use_kernel)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)
    # count / min / max honor the same routing
    cnt = ss.segment_reduce(jnp.asarray(gids), None, groups, op="count")
    np.testing.assert_array_equal(
        np.asarray(cnt), np.bincount(gids[(gids >= 0) & (gids < groups)],
                                     minlength=groups))
    mn = ss.segment_reduce(jnp.asarray(gids), jnp.asarray(vals), groups,
                           op="min")
    mask = (gids >= 0) & (gids < groups)
    for g in range(groups):
        rows = vals[mask & (gids == g)]
        if len(rows):
            assert np.isclose(np.asarray(mn)[g], rows.min())


@pytest.mark.parametrize("op", ["min", "max"])
def test_segment_minmax_kernel_matches_ref(op):
    rng = np.random.default_rng(11)
    gids = jnp.asarray(rng.integers(0, 130, 1000).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=1000).astype(np.float32))
    got = ss.segment_reduce(gids, vals, 130, op=op, use_kernel=True)
    want = ss.segment_reduce(gids, vals, 130, op=op, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# query-level: sortless engine == jnp-oracle engine == NumPy reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qid", [1, 4, 6, 12])
def test_hinted_queries_kernel_vs_oracle_paths(qid):
    """The hinted (sortless) plans must be byte-identical between the Pallas
    kernel path and the jnp scatter-reduce path, and match the reference."""
    from repro.queries import QUERIES
    db = tpch.generate(0.002, seed=5)
    r_k, _ = B.run_local(QUERIES[qid], db, use_kernel=True)
    r_j, _ = B.run_local(QUERIES[qid], db, use_kernel=False)
    assert set(r_k) == set(r_j)
    for k in r_k:
        np.testing.assert_allclose(np.asarray(r_k[k], np.float64),
                                   np.asarray(r_j[k], np.float64),
                                   rtol=1e-9, err_msg=f"q{qid} {k}")
    r_ref, _ = B.run_reference(QUERIES[qid], db)
    for k in set(r_ref) & set(r_k):
        np.testing.assert_allclose(np.asarray(r_k[k], np.float64),
                                   np.asarray(r_ref[k], np.float64),
                                   rtol=1e-7, err_msg=f"q{qid} {k} vs oracle")


# ---------------------------------------------------------------------------
# the kernels' fallback: masked reductions over small domains, else scatter
# ---------------------------------------------------------------------------

_MAX = ss.MASKED_REDUCE_MAX


def _fallback_case(op, dtype, groups, seed, n=700, all_invalid=False):
    """gids with negatives, the dead slot and ids past it, and the last of
    several slots left empty; values of ``dtype`` (None for count)."""
    rng = np.random.default_rng(seed)
    if all_invalid:
        gids = rng.choice([-7, -1, groups, groups + 1, groups + 40], n)
    else:
        gids = rng.integers(-3, groups + 4, n)
        if groups > 1:
            gids[gids == groups - 1] = -2
        gids[:4] = [-1, groups, groups + 5, 0]
    gids = jnp.asarray(gids.astype(np.int32))
    if op == "count":
        return gids, None
    if dtype == "float64":
        return gids, jnp.asarray(rng.uniform(0.0, 1000.0, n))
    return gids, jnp.asarray(rng.integers(-10**6, 10**6, n).astype(dtype))


def _check_fallback(op, gids, vals, groups):
    got = np.asarray(ss.segment_reduce(gids, vals, groups, op=op,
                                       use_kernel=False))
    if op == "count":
        want = segment_reduce_ref(gids, jnp.ones(gids.shape, jnp.int64),
                                     groups, "sum")
    else:
        want = segment_reduce_ref(gids, vals, groups, op)
    want = np.asarray(want)
    assert got.dtype == want.dtype and got.shape == (groups,)
    if op == "sum" and got.dtype.kind == "f":
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=0)
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("groups", [1, 8, 33, _MAX, _MAX + 1])
@pytest.mark.parametrize("dtype", ["float64", "int64", "int32"])
@pytest.mark.parametrize("op", ["sum", "count", "min", "max"])
def test_fallback_matches_scatter_ref(op, dtype, groups):
    """Up to MASKED_REDUCE_MAX slots the fallback is the masked path, one
    past it the scatter; both agree with the scatter oracle: ints and
    min/max exactly, float sums within 1e-12, empty groups at the
    identity, and out-of-range ids in no group."""
    gids, vals = _fallback_case(op, dtype, groups, seed=groups)
    assert ss.reduction_path(gids, vals, groups, op, use_kernel=False) == \
        ("masked" if groups <= _MAX else "scatter")
    _check_fallback(op, gids, vals, groups)


@pytest.mark.parametrize("groups", [1, 33])
@pytest.mark.parametrize("dtype", ["float64", "int64", "int32"])
@pytest.mark.parametrize("op", ["sum", "count", "min", "max"])
def test_fallback_all_rows_invalid(op, dtype, groups):
    """Every id negative, the dead slot or past it: every slot reads the
    reduction's identity, as the oracle's does."""
    gids, vals = _fallback_case(op, dtype, groups, seed=7, all_invalid=True)
    _check_fallback(op, gids, vals, groups)


@pytest.mark.parametrize("op,dtype,groups,cols,rows,interpret,path", [
    ("count", None, 32, 1, 6_000_000, False, "mxu"),
    ("count", None, 32, 1, 1 << 24, False, "masked"),
    ("sum", "float32", 8, 7, 6_000_000, False, "mxu"),
    ("min", "float32", 8, 1, 6_000_000, False, "mxu"),
    ("sum", "float64", 8, 7, 6_000_000, True, "mxu"),
    ("sum", "float64", 8, 7, 6_000_000, False, "masked"),
    ("sum", "int64", 8, 2, 6_000_000, False, "masked"),
    ("max", "int32", 8, 1, 6_000_000, False, "masked"),
    ("sum", "float64", _MAX // 7, 7, 6_000_000, False, "masked"),
    ("sum", "float64", _MAX // 7 + 1, 7, 6_000_000, False, "scatter"),
    ("sum", "float64", _MAX + 1, 1, 6_000_000, False, "scatter"),
])
def test_reduction_path_by_shape_and_dtype(op, dtype, groups, cols, rows,
                                           interpret, path):
    """The path a compiled (not interpreted) TPU program takes, from shapes
    and dtypes alone: counts and float32 on the kernels, float64 and ints
    masked while groups x columns <= MASKED_REDUCE_MAX, then scattered."""
    import jax
    gids = jax.ShapeDtypeStruct((rows,), jnp.int32)
    vals = None if dtype is None else \
        jax.ShapeDtypeStruct((rows, cols), jnp.dtype(dtype))
    assert ss.reduction_path(gids, vals, groups, op, interpret=interpret,
                             use_kernel=True) == path


@pytest.fixture(scope="module")
def small_server():
    from repro.serve.server import QueryServer
    return QueryServer(tpch.generate(0.01, seed=3))


@pytest.mark.parametrize("qid,masked", [(1, True), (5, True), (6, True),
                                        (12, True), (14, True), (19, True),
                                        (3, False), (18, False)])
def test_agg_reductions_by_template(small_server, qid, masked):
    """The served programs count their group-by reductions by path: the
    direct-path queries reduce every aggregate masked (no scatter); Q3's and
    Q18's group-bys take the sort path and count none masked."""
    assert small_server.agg_reductions(qid) is None      # not traced yet
    small_server.submit(qid)
    red = small_server.agg_reductions(qid)
    if masked:
        assert red.get("masked", 0) > 0 and "scatter" not in red, red
    else:
        assert "masked" not in red, red
