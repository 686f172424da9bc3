"""Sort-tax regression tests: deferred compaction + join-path equivalence.

Covers the three tentpole invariants:
  * masked (uncompacted) tables produce identical results to eagerly
    compacted ones across filter/join/group-by chains;
  * the Pallas hash-probe join path is byte-identical to the searchsorted
    path on all 22 TPC-H queries (with the NumPy RefContext as oracle), and
    the direct-address join index sorts nothing and loops nothing;
  * the HLO ``sort`` op count of representative local plans stays within the
    post-optimization budget (the CI gate runs the fuller check in
    ``benchmarks/bench_sort_tax.py``).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import backend as B
from repro.core import relational as R
from repro.core.table import Table, from_numpy, to_numpy
from repro.data import tpch
from repro.distributed.hlo_analysis import op_histogram
from repro.queries import QUERIES


@pytest.fixture(scope="module")
def db():
    return tpch.generate(0.005, seed=11)


def _rows(t):
    """Canonical row multiset of a table: sorted tuples over all columns."""
    d = to_numpy(t)
    names = sorted(d)
    rows = sorted(zip(*[d[n].tolist() for n in names]))
    return names, rows


def _random_table(seed, n=211, cap=256):
    rng = np.random.default_rng(seed)
    return from_numpy({
        "k": rng.integers(0, 15, n).astype(np.int64),
        "k2": rng.integers(0, 6, n).astype(np.int64),
        "v": rng.normal(size=n),
    }, capacity=cap)


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_masked_equals_compacted_filter_join_group_chain(seed):
    """Lazy-mask pipeline == the same pipeline with eager compaction after
    every operator (the seed engine's invariant)."""
    t = _random_table(seed)
    rng = np.random.default_rng(100 + seed)
    bn = 10
    build = from_numpy({"bk": np.arange(bn, dtype=np.int64),
                        "bv": rng.normal(size=bn)}, capacity=16)
    build = R.filter_rows(build, build["bk"] != 3)  # masked build side too

    def chain(t, build, eager):
        step = (lambda x: R.ensure_compact(x)) if eager else (lambda x: x)
        t = step(R.filter_rows(t, t["k"] < 12))
        t = step(R.join_unique(t, build, t["k"], build["bk"], ["bv"]))
        t = step(R.semi_join(t, build, t["k2"], build["bk"]))
        t = step(R.anti_join(t, build, t["k"] * 0 + 7, build["bk"])) \
            if seed % 2 else t
        g = R.group_aggregate(t, ["k", "k2"], [
            ("s", "sum", "v"), ("c", "count", None),
            ("mn", "min", "bv"), ("mx", "max", "v")])
        return R.sort_by(g, [("k", True), ("k2", False)])

    lazy = chain(t, build, eager=False)
    eager = chain(t, build, eager=True)
    nl, rl = _rows(lazy)
    ne, re_ = _rows(eager)
    assert nl == ne
    assert int(lazy.count) == int(eager.count)
    np.testing.assert_allclose(np.asarray(rl, dtype=np.float64),
                               np.asarray(re_, dtype=np.float64), rtol=1e-12)


def test_masked_count_invariant():
    """count == valid.sum() is preserved by every mask-producing op."""
    t = _random_table(7)
    f = R.filter_rows(t, t["v"] > 0)
    assert f.valid is not None
    assert int(f.count) == int(np.asarray(f.valid).sum())
    build = from_numpy({"bk": np.arange(5, dtype=np.int64)}, capacity=8)
    s = R.semi_join(f, build, f["k"], build["bk"])
    assert int(s.count) == int(np.asarray(s.valid).sum())
    c = R.ensure_compact(s)
    assert c.valid is None
    assert int(c.count) == int(s.count)


def test_sort_by_single_key_matches_multipass(db):
    """One multi-operand lax.sort == the seed's per-key passes (via numpy)."""
    t = _random_table(11)
    got = to_numpy(R.sort_by(t, [("k", True), ("v", False), ("k2", True)]))
    d = to_numpy(t)
    order = np.lexsort((d["k2"], -d["v"], d["k"]))
    for c in ("k", "k2", "v"):
        np.testing.assert_array_equal(got[c], d[c][order])


def _masked(t, seed):
    keep = jnp.asarray(np.random.default_rng(seed).random(t.capacity) < 0.6)
    keep = keep & t.valid_mask()
    return Table(t.columns, keep.sum().astype(jnp.int32), keep)


_ORDER_KEYS = [[("k", True)], [("v", False), ("k", True)],
               [("k", False), ("k2", True), ("v", True)]]


def _order_ops(t, keys):
    return tuple(R._order_operands(t, keys))


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("keys", _ORDER_KEYS)
def test_sort_by_pairwise_ranks_match_lax_sort(keys, masked):
    """Ranking rows pairwise (sort_by's TPU path) orders them exactly as the
    stable lax.sort does, invalid rows and ties included."""
    t = _random_table(5)
    t = _masked(t, 6) if masked else t
    ops = _order_ops(t, keys)
    np.testing.assert_array_equal(np.asarray(R._pairwise_order(*ops)),
                                  np.asarray(R._sort_order(*ops)))


@pytest.mark.parametrize("n", [1, 7, 100, 256, 300])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("keys", _ORDER_KEYS)
def test_sort_limit_matches_sort_then_limit(keys, masked, n):
    """ORDER BY ... LIMIT n picks the same rows in the same order as the
    full sort followed by LIMIT; sort_limit's TPU path (n rounds of
    picking the smallest row) picks the stable sort's first n."""
    t = _random_table(7)
    t = _masked(t, 8) if masked else t
    got = R.sort_limit(t, keys, n)
    want = R.limit(R.sort_by(t, keys), n)
    assert int(got.count) == int(want.count)
    for c in ("k", "k2", "v"):
        np.testing.assert_array_equal(np.asarray(got[c]),
                                      np.asarray(want[c]))
    ops = _order_ops(t, keys)
    m = min(n, t.capacity)
    np.testing.assert_array_equal(np.asarray(R._smallest_rows(ops, m)),
                                  np.asarray(R._sort_order(*ops)[:m]))


def test_combine_keys_bits_packing():
    a = jnp.asarray([1, 2, 3], dtype=jnp.int64)
    b = jnp.asarray([4, 5, 6], dtype=jnp.int64)
    c = jnp.asarray([7, 0, 1], dtype=jnp.int64)
    k = R.combine_keys([a, b, c], bits=[8, 8, 8])
    np.testing.assert_array_equal(
        np.asarray(k), ((np.array([1, 2, 3]) << 8 | [4, 5, 6]) << 8) | [7, 0, 1])
    with pytest.raises(ValueError):
        R.combine_keys([a, b, c], bits=[32, 31, 8])
    with pytest.raises(ValueError):
        R.combine_keys([a, b, c])


@pytest.mark.parametrize("qid", sorted(QUERIES))
def test_hash_join_path_byte_identical(db, qid):
    """Kernel-backed hash-probe joins == searchsorted joins, bit for bit,
    and both match the NumPy reference oracle."""
    r_sorted, _ = B.run_local(QUERIES[qid], db, join_method="sorted")
    r_hash, _ = B.run_local(QUERIES[qid], db, join_method="hash")
    assert set(r_sorted) == set(r_hash)
    for k in r_sorted:
        np.testing.assert_array_equal(r_sorted[k], r_hash[k],
                                      err_msg=f"q{qid} {k}")
    r_ref, _ = B.run_reference(QUERIES[qid], db)
    for k in set(r_ref) & set(r_hash):
        np.testing.assert_allclose(np.asarray(r_hash[k], np.float64),
                                   np.asarray(r_ref[k], np.float64),
                                   rtol=1e-7, err_msg=f"q{qid} {k} vs oracle")


# Absolute per-query HLO sort budgets for the local plans (phase 2/3:
# planner-inferred group-bys are sortless, shuffle dispatch is sortless,
# joins on a proven dense build key use the direct-address index, which
# sorts nothing).  Tighter than the seed-relative 40% rule; the fuller gate
# lives in benchmarks/bench_sort_tax.py.  Compiled with inference pinned ON
# so the REPRO_PLANNER=0 CI leg measures the same program.
#   q1  = 1 final ORDER BY              (group-by direct, was 2)
#   q3  = 1 final ORDER BY   (both build indexes direct; the l_orderkey
#         group-by is direct at this SF — 2 where it is not, as at the
#         bench's sf 0.01; was 4)
#   q5  = 1 final ORDER BY              (five direct build indexes)
#   q6  = 0 (scalar aggregation is the trivial direct domain)
#   q9  = 1 build index (the two-column partsupp key: sorted) + 1 final
#         ORDER BY (group-by direct, was 6, then 5)
#   q10 = 1 final ORDER BY              (both build indexes direct)
#   q12 = 1 final ORDER BY   (build index direct, group-by direct, was 3)
#   q13 = 1 final ORDER BY   (build index direct; the c_count group-by
#         rides the hash-compaction dictionary — data-dependent domain,
#         zero sorts — and the o_custkey group-by is direct; was 3)
#   q14 = 0, q19 = 0 (one direct build index, scalar aggregation)
#   q18 = 1 compaction (the shrink before the broadcast) + 1 final ORDER
#         BY (both build indexes direct)
_MAX_SORTS = {1: 1, 3: 1, 5: 1, 6: 0, 9: 2, 10: 1, 12: 1, 13: 1, 14: 0,
              18: 2, 19: 0}


@pytest.mark.parametrize("qid", sorted(_MAX_SORTS))
def test_hlo_sort_count_budget(db, qid):
    tables = B._np_db_to_tables(db)

    def run(tables):
        ctx = B.LocalContext(db, tables)
        out = QUERIES[qid].run(ctx, infer=True)
        if isinstance(out, dict):
            out = Table({k: jnp.asarray(v).reshape(1) for k, v in out.items()},
                        jnp.asarray(1, jnp.int32))
        return R.ensure_compact(out), ctx.overflow

    hlo = jax.jit(run).lower(tables).compile().as_text()
    nsort = op_histogram(hlo, ops=("sort",))["sort"]
    assert nsort <= _MAX_SORTS[qid], \
        f"q{qid}: {nsort} HLO sorts > budget {_MAX_SORTS[qid]}"


def test_direct_join_zero_sorts_no_loop():
    """A join through the direct-address index lowers to ZERO HLO sorts and
    no while loop: one scatter builds it, one gather probes it (the sorted
    index's argsort and searchsorted loop are both gone)."""
    t = _random_table(12)
    build = from_numpy({"bk": np.arange(3, 18, dtype=np.int64),
                        "bv": np.arange(15) * 0.5}, capacity=16)

    def run(t, build):
        idx = R.build_index(build, build["bk"], method="direct",
                            key_range=(3, 17))
        assert idx.method == "direct"
        out = R.join_unique(t, build, t["k"], build["bk"], ["bv"],
                            index=idx)
        return out["bv"], out.valid_mask(), idx.overflow

    hlo = jax.jit(run).lower(t, build).compile().as_text()
    counts = op_histogram(hlo, ops=("sort", "while"))
    assert counts == {"sort": 0, "while": 0}, counts


def test_group_aggregate_with_key_bits_zero_sorts():
    """The direct-addressing path must lower to ZERO HLO sorts."""
    t = _random_table(13)

    def run(t):
        return R.group_aggregate(t, ["k", "k2"], [
            ("s", "sum", "v"), ("c", "count", None),
            ("mn", "min", "v"), ("mx", "max", "v")], key_bits=[4, 3])

    hlo = jax.jit(run).lower(t).compile().as_text()
    assert op_histogram(hlo, ops=("sort",))["sort"] == 0


@pytest.mark.parametrize("use_kernel", [True, False])
def test_group_aggregate_hash_path_zero_sorts(use_kernel):
    """The hash-compaction path (groups_hint, NO key_bits) must lower to ZERO
    HLO sorts on BOTH aggregation engines — dictionary build, ascending-key
    rank derivation, and the segsum reduce are all sort-free."""
    rng = np.random.default_rng(15)
    t = from_numpy({"k": rng.integers(0, 1 << 40, 211).astype(np.int64),
                    "v": rng.normal(size=211)}, capacity=256)

    def run(t):
        return R.group_aggregate(t, ["k"], [
            ("s", "sum", "v"), ("c", "count", None),
            ("mn", "min", "v"), ("mx", "max", "v")],
            method="hash", groups_hint=256, use_kernel=use_kernel,
            return_overflow=True)

    hlo = jax.jit(run).lower(t).compile().as_text()
    assert op_histogram(hlo, ops=("sort",))["sort"] == 0


def test_scalar_aggregate_zero_sorts():
    t = _random_table(14)

    def run(t):
        return R.group_aggregate(t, [], [("s", "sum", "v"),
                                         ("c", "count", None)])

    hlo = jax.jit(run).lower(t).compile().as_text()
    assert op_histogram(hlo, ops=("sort",))["sort"] == 0


def test_shuffle_dispatch_zero_sorts():
    """Counting-rank destination dispatch must lower to ZERO HLO sorts."""
    from repro.core import exchange as EX
    dest = jnp.asarray(np.random.default_rng(0).integers(0, 9, 512),
                       jnp.int32)

    def run(d):
        return EX._dispatch_offsets(d, 8)

    hlo = jax.jit(run).lower(dest).compile().as_text()
    assert op_histogram(hlo, ops=("sort",))["sort"] == 0
