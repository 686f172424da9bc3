"""Unit tests: static-shape relational ops vs the NumPy reference."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import reference as REF
from repro.core import relational as R
from repro.core.table import from_numpy, to_numpy


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    n = 153
    return {
        "k": rng.integers(0, 12, n).astype(np.int64),
        "k2": rng.integers(0, 5, n).astype(np.int64),
        "v": rng.normal(size=n),
        "q": rng.integers(1, 50, n).astype(np.int64),
    }


def test_filter_matches_reference(data):
    t = from_numpy(data, capacity=256)
    got = to_numpy(R.filter_rows(t, (t["k"] < 6) & (t["q"] > 10)))
    want = REF.filter_rows(data, (data["k"] < 6) & (data["q"] > 10))
    assert got["v"].shape == want["v"].shape
    np.testing.assert_allclose(np.sort(got["v"]), np.sort(want["v"]))


def test_group_aggregate_all_ops(data):
    t = from_numpy(data, capacity=256)
    aggs = [("s", "sum", "v"), ("c", "count", None),
            ("mn", "min", "v"), ("mx", "max", "v")]
    got = to_numpy(R.group_aggregate(t, ["k", "k2"], aggs))
    want = REF.group_aggregate(data, ["k", "k2"], aggs)
    o = np.lexsort((got["k2"], got["k"]))
    ow = np.lexsort((want["k2"], want["k"]))
    for c in ("s", "c", "mn", "mx"):
        np.testing.assert_allclose(got[c][o], want[c][ow], rtol=1e-12)


def test_join_semi_anti_left(data):
    t = from_numpy(data, capacity=256)
    bcols = {"bk": np.arange(8, dtype=np.int64), "bv": np.arange(8) * 2.0}
    b = from_numpy(bcols, capacity=16)
    got = to_numpy(R.join_unique(t, b, t["k"], b["bk"], ["bv"]))
    want = REF.join_unique(data, bcols, data["k"], bcols["bk"], ["bv"])
    assert got["bv"].shape == want["bv"].shape
    np.testing.assert_allclose(np.sort(got["bv"] + got["v"]),
                               np.sort(want["bv"] + want["v"]))
    sg = to_numpy(R.semi_join(t, b, t["k"], b["bk"]))
    sw = REF.semi_join(data, bcols, data["k"], bcols["bk"])
    assert sg["k"].shape == sw["k"].shape
    ag = to_numpy(R.anti_join(t, b, t["k"], b["bk"]))
    aw = REF.anti_join(data, bcols, data["k"], bcols["bk"])
    assert ag["k"].shape == aw["k"].shape
    lg = to_numpy(R.left_join(t, b, t["k"], b["bk"], ["bv"], {"bv": -1.0}))
    lw = REF.left_join(data, bcols, data["k"], bcols["bk"], ["bv"],
                       {"bv": -1.0})
    np.testing.assert_allclose(np.sort(lg["bv"]), np.sort(lw["bv"]))


def test_join_rejects_duplicate_build_keys():
    b = {"bk": np.array([1, 1, 2], dtype=np.int64), "bv": np.zeros(3)}
    p = {"k": np.array([1, 2], dtype=np.int64)}
    with pytest.raises(ValueError):
        REF.join_unique(p, b, p["k"], b["bk"], ["bv"])


def test_sort_by_multikey(data):
    t = from_numpy(data, capacity=256)
    got = to_numpy(R.sort_by(t, [("k", True), ("v", False)]))
    want = REF.sort_by(data, [("k", True), ("v", False)])
    np.testing.assert_allclose(got["v"], want["v"])
    np.testing.assert_array_equal(got["k"], want["k"])


def test_static_shrink_overflow_flag(data):
    t = from_numpy(data, capacity=256)
    small, ov = R.static_shrink(t, 64)
    assert bool(ov) and small.capacity == 64
    big, ov2 = R.static_shrink(t, 200)
    assert not bool(ov2) and int(big.count) == len(data["k"])


def test_combine_keys_rejects_three():
    with pytest.raises(ValueError):
        R.combine_keys([jnp.arange(3)] * 3)
    with pytest.raises(ValueError):
        REF.combine_keys([np.arange(3)] * 3)


def test_limit_and_valid_mask(data):
    t = from_numpy(data, capacity=256)
    l5 = R.limit(R.sort_by(t, [("v", True)]), 5)
    got = to_numpy(l5)
    want = np.sort(data["v"])[:5]
    np.testing.assert_allclose(got["v"], want)


# ---------------------------------------------------------------------------
# direct-address join index == sorted index
# ---------------------------------------------------------------------------

_SENT = np.iinfo(np.int64).max
_MIN = np.iinfo(np.int64).min

# (lo, hi) of the build keys' domain per case, and the index it takes
_DIRECT_CASES = {
    "dense_from_1": (1, 64, "direct"),
    "offset_lo": (1_000_000, 1_000_300, "direct"),
    "negative_lo": (-200, 100, "direct"),
    "duplicates": (5, 30, "direct"),
    "near_int64_max": (_SENT - 300, _SENT - 1, "direct"),
    "int64_extremes": (_MIN + 2000, _SENT - 2000, "sorted"),
}


def _direct_case(case):
    """(build table, probe table, key_range) of one direct-index case."""
    rng = np.random.default_rng(sorted(_DIRECT_CASES).index(case))
    lo, hi, _ = _DIRECT_CASES[case]
    nb = 40
    if case == "duplicates":
        bk = rng.integers(lo, hi + 1, nb)
    elif hi - lo < 1 << 20:
        bk = lo + rng.choice(hi - lo + 1, nb, replace=False)
    else:
        bk = np.unique(rng.integers(lo, hi, nb, dtype=np.int64))
        nb = bk.size
    edges = [lo, hi, lo - 1, hi + 1, lo - 1000, hi + 1000]
    probe = np.concatenate([
        rng.choice(bk, 60),                       # matches
        rng.integers(lo, hi, 30, dtype=np.int64),  # mostly unmatched
        np.asarray([e for e in edges if _MIN <= e < _SENT] + [_SENT],
                   dtype=np.int64)])
    bcols = {"bk": bk.astype(np.int64), "bv": rng.normal(size=nb),
             "bi": rng.integers(0, 1 << 40, nb)}
    build = from_numpy(bcols, capacity=48)
    # invalid build rows: masked out, some keys outside the range
    keep = jnp.asarray(rng.random(48) < 0.8) & build.valid_mask()
    cols = dict(build.columns)
    cols["bk"] = jnp.where(~keep & (jnp.arange(48) % 2 == 0), lo - 5,
                           cols["bk"])
    build = R.Table(cols, keep.sum().astype(jnp.int32), keep)
    p = from_numpy({"k": probe, "pv": rng.normal(size=probe.size)},
                   capacity=128)
    pkeep = jnp.asarray(rng.random(128) < 0.9) & p.valid_mask()
    p = R.Table(p.columns, pkeep.sum().astype(jnp.int32), pkeep)
    return build, p, (lo, hi)


def _valid_rows(t):
    """Valid rows of a table, in order (masked rows dropped)."""
    d = to_numpy(t)
    return {k: np.asarray(v) for k, v in d.items()}


@pytest.mark.parametrize("case", sorted(_DIRECT_CASES))
def test_direct_index_matches_sorted(case):
    """The direct-address index gives the sorted index's answers bit for
    bit: the same matches, the same build rows, the same joined tables."""
    build, p, rng_ = _direct_case(case)
    direct = R.build_index(build, build["bk"], method="direct",
                           key_range=rng_)
    sorted_ = R.build_index(build, build["bk"], method="sorted")
    assert direct.method == _DIRECT_CASES[case][2]
    assert not bool(direct.overflow)
    md, rd = R.probe_index(direct, p["k"], p.valid_mask())
    ms, rs = R.probe_index(sorted_, p["k"], p.valid_mask())
    np.testing.assert_array_equal(np.asarray(md), np.asarray(ms))
    m = np.asarray(ms)
    assert m.any() and not m.all()
    np.testing.assert_array_equal(np.asarray(rd)[m], np.asarray(rs)[m])
    ops = [("semi", lambda idx: R.semi_join(p, build, p["k"], build["bk"],
                                            index=idx)),
           ("anti", lambda idx: R.anti_join(p, build, p["k"], build["bk"],
                                            index=idx))]
    if case != "duplicates":        # inner and left joins need unique keys
        ops += [("join", lambda idx: R.join_unique(
                    p, build, p["k"], build["bk"], ["bv", "bi"], index=idx)),
                ("left", lambda idx: R.left_join(
                    p, build, p["k"], build["bk"], ["bv", "bi"],
                    {"bv": -1.0, "bi": -1}, index=idx))]
    for name, op in ops:
        got, want = _valid_rows(op(direct)), _valid_rows(op(sorted_))
        assert set(got) == set(want), name
        for k in want:
            np.testing.assert_array_equal(got[k], want[k],
                                          err_msg=f"{case} {name} {k}")


def test_direct_index_build_key_outside_range_sets_overflow():
    """A valid build key outside the claimed range raises the overflow flag
    (the conservative rerun's signal); an invalid row's key does not."""
    b = from_numpy({"bk": np.arange(10, 20, dtype=np.int64)}, capacity=16)
    ok = R.build_index(b, b["bk"], method="direct", key_range=(10, 19))
    assert ok.method == "direct" and not bool(ok.overflow)
    for rng_ in ((10, 18), (11, 19)):
        lying = R.build_index(b, b["bk"], method="direct", key_range=rng_)
        assert lying.method == "direct" and bool(lying.overflow), rng_
    masked = R.filter_rows(b, b["bk"] < 19)        # key 19 now invalid
    idx = R.build_index(masked, masked["bk"], method="direct",
                        key_range=(10, 18))
    assert not bool(idx.overflow)


@pytest.mark.parametrize("span,method", [
    (R.DIRECT_JOIN_SPAN * 16, "direct"),
    (R.DIRECT_JOIN_SPAN * 16 + 1, "sorted"),
    (R.DIRECT_JOIN_SLOTS_MAX + 1, "sorted"),
    (0, "sorted"),
])
def test_direct_index_span_rule(span, method):
    """The direct index takes at most DIRECT_JOIN_SPAN slots per build row
    (and DIRECT_JOIN_SLOTS_MAX in all); a sparser domain falls back to the
    sorted index, with the same answers."""
    b = from_numpy({"bk": np.arange(1, 11, dtype=np.int64)}, capacity=16)
    rng_ = (1, span)
    if span > R.DIRECT_JOIN_SLOTS_MAX:          # a huge build, never built
        assert not R.direct_join_fits(rng_, R.DIRECT_JOIN_SLOTS_MAX)
        rng_ = (1, R.DIRECT_JOIN_SLOTS_MAX + 1)
    assert R.direct_join_fits(rng_, b.capacity) == (method == "direct")
    if span > R.DIRECT_JOIN_SLOTS_MAX:
        return
    idx = R.build_index(b, b["bk"], method="direct", key_range=rng_)
    assert idx.method == method
    pk = jnp.arange(0, 14, dtype=jnp.int64)
    m, _ = R.probe_index(idx, pk, jnp.ones(14, bool))
    want = (np.arange(14) >= 1) & (np.arange(14) <= 10)
    np.testing.assert_array_equal(np.asarray(m), want)
