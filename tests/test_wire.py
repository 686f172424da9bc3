"""Wire-format tests: stats-driven exchange payload compression.

Four layers of assertion:

  * **Layout** — the wide layout reproduces the legacy packing exactly; the
    narrow layout never exceeds it; mode selection follows the documented
    lane rules.
  * **Round-trip** — pack/unpack is lossless on every valid row across all
    dtypes x widths x masked tables (hypothesis), with a statically-false
    overflow flag when the bounds are truthful.
  * **Overflow contract** — lying bounds must trip the overflow flag (pack
    level, exchange level under a real collective, and a full distributed
    query with planner statistics overridden) — never silently truncate.
  * **Static == runtime** — the IR-derived wire descriptors
    (``planner.static_wire_stats``) equal the ``ExchangeStats`` every backend
    logs, entry for entry, and the distributed narrow format is byte-
    identical to wide on real exchanges (the full 22-query x 8-device sweep
    is the slow leg in tests/test_distributed.py).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import backend as B
from repro.core import planner as PL
from repro.core import wire as W
from repro.core.compat import make_mesh
from repro.core.relational import filter_rows
from repro.core.table import from_numpy
from repro.data import tpch
from repro.queries import QUERIES


@pytest.fixture(scope="module")
def db():
    return tpch.generate(0.005, seed=11)


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh((1,), ("data",))


def _mktable(rng, n=80, cap=96):
    cols = {
        "k64": rng.integers(0, 200, n).astype(np.int64),
        "wide64": (rng.integers(0, 1 << 40, n)).astype(np.int64),
        "mid64": (rng.integers(100_000, 1 << 25, n)).astype(np.int64),
        "i32": rng.integers(-50, 900, n).astype(np.int32),
        "f64": rng.normal(size=n),
        "f32": rng.normal(size=n).astype(np.float32),
        "b": rng.integers(0, 2, n).astype(bool),
        "c": np.full(n, -7, np.int64),
    }
    return cols, from_numpy(cols, capacity=cap)


def _true_bounds(cols):
    return {n: (int(v.min()), int(v.max())) for n, v in cols.items()
            if np.issubdtype(v.dtype, np.integer)}


# ---------------------------------------------------------------------------
# layout
# ---------------------------------------------------------------------------

def test_wide_layout_matches_legacy_packing():
    """Wide = one word per 4 logical bytes, bool widened, sorted-name order."""
    dt = {"a": np.dtype(np.int64), "b": np.dtype(bool),
          "c": np.dtype(np.float64), "d": np.dtype(np.int32)}
    fmt = W.plan_wire_format(dt, dt, bounds=None, narrow=False)
    assert fmt.words == 2 + 1 + 2 + 1
    modes = {c.name: (c.mode, c.word) for c in fmt.cols}
    assert modes == {"a": ("split", 0), "b": ("word", 2),
                     "c": ("split", 3), "d": ("word", 5)}
    assert fmt.row_wire_bytes == 24 and fmt.row_logical_bytes == 21


def test_f64_words_tpu_pair_roundtrip():
    """TPU float64 has no IEEE bit pattern, so it ships as its float32 pair;
    every value that is such a pair (all a TPU holds) round-trips exactly."""
    rng = np.random.default_rng(0)
    hi = (rng.normal(size=64) * 1e5).astype(np.float32)
    lo = (hi * rng.uniform(-2.0 ** -25, 2.0 ** -25, 64)).astype(np.float32)
    v = np.concatenate([hi.astype(np.float64) + lo, [0.0, np.inf, -np.inf]])
    words = W._f64_pair(jnp.asarray(v))
    assert words.shape == (67, 2) and words.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(W._pair_f64(words)), v)


def test_narrow_mode_selection_and_lane_sharing():
    dt = {"dict8": np.dtype(np.int32), "date16": np.dtype(np.int64),
          "key32": np.dtype(np.int64), "flag": np.dtype(bool),
          "price": np.dtype(np.float64), "konst": np.dtype(np.int64)}
    bounds = {"dict8": (0, 24), "date16": (8000, 10500),
              "key32": (1, 1 << 20), "konst": (5, 5)}
    fmt = W.plan_wire_format(dt, dt, bounds, narrow=True)
    modes = {c.name: c.mode for c in fmt.cols}
    assert modes == {"dict8": "lane8", "date16": "lane16", "key32": "u32",
                     "flag": "lane8", "price": "split", "konst": "const"}
    # 16-bit lane + two 8-bit lanes share ONE word; const ships nothing
    lane_words = {c.word for c in fmt.cols if c.mode.startswith("lane")}
    assert len(lane_words) == 1
    assert fmt.words == 1 + 1 + 2        # lanes + u32 + f64 split
    assert fmt.row_wire_bytes == 16 and fmt.row_logical_bytes == 37


def test_narrow_never_exceeds_wide():
    rng = np.random.default_rng(0)
    cols, _ = _mktable(rng)
    dt = {n: v.dtype for n, v in cols.items()}
    for bounds in (None, {}, _true_bounds(cols)):
        nf = W.plan_wire_format(cols, dt, bounds, narrow=True)
        wf = W.plan_wire_format(cols, dt, bounds, narrow=False)
        assert nf.words <= wf.words
        assert nf.row_logical_bytes == wf.row_logical_bytes


# ---------------------------------------------------------------------------
# round-trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("narrow", [True, False])
@pytest.mark.parametrize("masked", [True, False])
def test_pack_unpack_roundtrip_all_modes(seed, narrow, masked):
    rng = np.random.default_rng(seed)
    cols, t = _mktable(rng)
    if masked:
        t = filter_rows(t, t["k64"] < 150)
    fmt = W.plan_wire_format(cols, {n: v.dtype for n, v in cols.items()},
                             _true_bounds(cols), narrow=narrow)
    buf, overflow = W.pack_table(t, fmt)
    assert not bool(overflow), "truthful bounds must never overflow"
    back = W.unpack_table(buf, fmt)
    m = np.asarray(t.valid_mask())
    for n in cols:
        np.testing.assert_array_equal(np.asarray(back[n])[m],
                                      np.asarray(t[n])[m], err_msg=n)


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:                                     # pragma: no cover
    HAVE_HYP = False


if HAVE_HYP:
    @st.composite
    def bounded_tables(draw):
        n = draw(st.integers(1, 60))
        rng = np.random.default_rng(draw(st.integers(0, 2**31)))
        hi8 = draw(st.integers(0, 255))
        hi16 = draw(st.integers(256, 65_535))
        lo = draw(st.integers(-(1 << 40), 1 << 40))
        span = draw(st.integers(0, 1 << 33))
        cols = {
            "a": rng.integers(0, hi8 + 1, n).astype(np.int64),
            "b": rng.integers(0, hi16 + 1, n).astype(np.int32)
            if hi16 <= (1 << 31) - 1 else
            rng.integers(0, hi16 + 1, n).astype(np.int64),
            "c": rng.integers(lo, lo + span + 1, n).astype(np.int64),
            "v": rng.normal(size=n),
            "f": rng.normal(size=n).astype(np.float32),
            "m": rng.integers(0, 2, n).astype(bool),
        }
        mask_frac = draw(st.floats(0.0, 1.0))
        return cols, mask_frac, rng

    @settings(max_examples=40, deadline=None)
    @given(bounded_tables(), st.booleans())
    def test_roundtrip_property(args, narrow):
        """Lossless on valid rows for every dtype/width/mask combination."""
        cols, mask_frac, rng = args
        n = len(cols["a"])
        t = from_numpy(cols, capacity=max(8, n + 3))
        keep = rng.random(t.capacity) >= mask_frac
        t = filter_rows(t, jnp.asarray(keep))
        fmt = W.plan_wire_format(cols, {k: v.dtype for k, v in cols.items()},
                                 _true_bounds(cols), narrow=narrow)
        buf, overflow = W.pack_table(t, fmt)
        assert not bool(overflow)
        back = W.unpack_table(buf, fmt)
        m = np.asarray(t.valid_mask())
        for name in cols:
            np.testing.assert_array_equal(np.asarray(back[name])[m],
                                          np.asarray(t[name])[m],
                                          err_msg=f"{name} narrow={narrow}")


# ---------------------------------------------------------------------------
# overflow contract (lying bounds)
# ---------------------------------------------------------------------------

def test_lying_bounds_trip_overflow_at_pack():
    rng = np.random.default_rng(7)
    cols, t = _mktable(rng)
    bounds = _true_bounds(cols)
    lo, hi = bounds["k64"]
    for lie in [(lo, max(lo, hi // 4)), (lo + 1, hi), (hi + 1, hi + 2)]:
        bad = dict(bounds)
        bad["k64"] = lie
        fmt = W.plan_wire_format(cols, {n: v.dtype for n, v in cols.items()},
                                 bad, narrow=True)
        _, overflow = W.pack_table(t, fmt)
        assert bool(overflow), f"lie {lie} must trip overflow"


def test_lying_bounds_only_checked_on_valid_rows():
    """Garbage in masked rows must NOT trip the range check."""
    rng = np.random.default_rng(8)
    cols, t = _mktable(rng)
    # mask out every row whose k64 exceeds 20, then claim (0, 20): truthful
    # for the surviving rows even though masked rows violate it
    t = filter_rows(t, t["k64"] <= 20)
    bounds = dict(_true_bounds(cols))
    bounds["k64"] = (0, 20)
    fmt = W.plan_wire_format(cols, {n: v.dtype for n, v in cols.items()},
                             bounds, narrow=True)
    buf, overflow = W.pack_table(t, fmt)
    assert not bool(overflow)
    back = W.unpack_table(buf, fmt)
    m = np.asarray(t.valid_mask())
    np.testing.assert_array_equal(np.asarray(back["k64"])[m],
                                  np.asarray(t["k64"])[m])


def test_lying_bounds_trip_ctx_overflow_distributed(db, mesh1):
    """A full distributed query with a lying planner statistic must surface
    ctx.overflow (the fault runner's re-execution signal), never silently
    truncate: Q3's broadcast ships c_custkey, whose claimed width we break."""
    stats = dict(PL.column_stats(db))
    real = stats["c_custkey"]
    stats["c_custkey"] = PL.ColStats(real.lo, max(real.lo, real.hi // 8), None)
    with PL.stats_override(db, stats):
        _, _, ov = B.run_distributed(QUERIES[3].with_inference(True), db,
                                     mesh1, capacity_factor=3.0,
                                     wire_format="narrow")
    assert ov, "lying wire bounds must raise the overflow flag"
    # sanity: with honest statistics the same plan runs clean
    _, _, ov = B.run_distributed(QUERIES[3].with_inference(True), db, mesh1,
                                 capacity_factor=3.0, wire_format="narrow")
    assert not ov


# ---------------------------------------------------------------------------
# static == runtime, narrow == wide
# ---------------------------------------------------------------------------

def _entries(stats):
    return [(e.kind, e.wire, e.row_wire_bytes, e.row_logical_bytes)
            for e in stats.log]


def _static(qid, db, narrow):
    return [(d["kind"], d["wire"], d["row_wire_bytes"],
             d["row_logical_bytes"])
            for d in QUERIES[qid].static_wire(db, narrow=narrow)]


@pytest.mark.parametrize("qid", sorted(QUERIES))
def test_static_wire_stats_equal_reference_runtime(db, qid):
    """IR-derived wire descriptors == what execution records, both formats."""
    for wf in ("narrow", "wide"):
        _, stats = B.run_reference(QUERIES[qid].with_inference(True),
                                   db, wire_format=wf)
        assert _entries(stats) == _static(qid, db, wf == "narrow"), (qid, wf)


@pytest.mark.parametrize("qid", [2, 5, 9, 18, 22])
def test_static_wire_stats_equal_local_runtime(db, qid):
    for wf in ("narrow", "wide"):
        _, stats = B.run_local(QUERIES[qid].with_inference(True), db,
                               wire_format=wf)
        assert _entries(stats) == _static(qid, db, wf == "narrow"), (qid, wf)


@pytest.mark.parametrize("qid", [3, 5, 9, 18])
def test_distributed_narrow_equals_wide_and_static(db, mesh1, qid):
    """Real collectives (1-device mesh): the narrow format is byte-identical
    to wide, matches the NumPy oracle, logs ONE collective per packed
    exchange (fused counts header), and reports the static wire bytes."""
    q = QUERIES[qid].with_inference(True)
    r_ref, _ = B.run_reference(q, db)
    r_n, s_n, ov_n = B.run_distributed(q, db, mesh1, capacity_factor=3.0,
                                       wire_format="narrow")
    r_w, s_w, ov_w = B.run_distributed(q, db, mesh1, capacity_factor=3.0,
                                       wire_format="wide")
    assert not ov_n and not ov_w
    assert set(r_n) == set(r_w)
    for k in r_n:
        np.testing.assert_array_equal(r_n[k], r_w[k], err_msg=f"q{qid} {k}")
    for k in set(r_ref) & set(r_n):
        np.testing.assert_allclose(np.asarray(r_n[k], np.float64),
                                   np.asarray(r_ref[k], np.float64),
                                   rtol=1e-7, err_msg=f"q{qid} {k}")
    assert _entries(s_n) == _static(qid, db, True), qid
    assert _entries(s_w) == _static(qid, db, False), qid
    # metadata round fused into the payload: one collective per exchange
    assert all(e.collectives == 1 for e in s_n.log), \
        [(e.kind, e.collectives) for e in s_n.log]
    # wire bytes on the wire really shrank vs the wide leg
    assert sum(e.message_bytes for e in s_n.log) < \
        sum(e.message_bytes for e in s_w.log)


# ---------------------------------------------------------------------------
# Hockney-driven packing skip (REPRO_HOCKNEY)
# ---------------------------------------------------------------------------

def test_hockney_skip_thresholds(monkeypatch):
    from repro.core import perfmodel as PM
    monkeypatch.delenv("REPRO_HOCKNEY", raising=False)
    assert PM.hockney_from_env() is None
    assert not W.hockney_skip(24)
    # 10us latency, 1ns/B: a 4096-row x 24B message costs ~98us on the wire
    # -> bandwidth-bound, packing pays
    monkeypatch.setenv("REPRO_HOCKNEY", "1e-5,1e-9")
    assert not W.hockney_skip(24)
    # 1ms latency: the same message sits below the half-bandwidth point
    monkeypatch.setenv("REPRO_HOCKNEY", "1e-3,1e-9")
    assert W.hockney_skip(24)
    # explicit msg_rows field: one-row messages are latency-bound even at 10us
    monkeypatch.setenv("REPRO_HOCKNEY", "1e-5,1e-9,1")
    assert W.hockney_skip(24)


def test_hockney_latency_bound_message_ships_wide(monkeypatch):
    dt = {"dict8": np.dtype(np.int32), "key32": np.dtype(np.int64)}
    bounds = {"dict8": (0, 24), "key32": (1, 1 << 20)}
    monkeypatch.setenv("REPRO_HOCKNEY", "1.0,1e-9")
    fmt = W.plan_wire_format(dt, dt, bounds, narrow=True)
    assert not fmt.narrow and fmt.row_wire_bytes == 12   # wide: 1 + 2 words
    monkeypatch.delenv("REPRO_HOCKNEY")
    fmt = W.plan_wire_format(dt, dt, bounds, narrow=True)
    assert fmt.narrow and fmt.row_wire_bytes < 12


def test_hockney_skip_static_equals_runtime(db, monkeypatch):
    """The skip is priced from per-row widths + the env model alone, so the
    IR-derived report and every backend reach the same wide verdict."""
    monkeypatch.setenv("REPRO_HOCKNEY", "1.0,1e-9")
    for qid in (3, 9):
        _, stats = B.run_reference(QUERIES[qid].with_inference(True), db,
                                   wire_format="narrow")
        got = _entries(stats)
        assert got == _static(qid, db, True), qid
        assert got and all(e[1] == "wide" for e in got), got


def test_unpacked_mode_keeps_metadata_round(db, mesh1):
    """Paper-faithful per-column exchange: one collective per column PLUS the
    size-metadata round (the §2.3 baseline the fused header removes)."""
    _, s_col, ov = B.run_distributed(QUERIES[9].with_inference(True), db,
                                     mesh1, capacity_factor=3.0,
                                     packed_exchange=False)
    assert not ov
    for e in s_col.log:
        if e.kind == "broadcast_p2p":
            continue
        assert e.collectives > 1, (e.kind, e.collectives)
        assert e.wire == "wide"
