"""Chaos harness tests: seeded fault injection, the failure taxonomy, the
policy-driven QueryRunner, wire integrity checksums and lineage recovery.

Layers:

  * **Checksum** — the rotated-XOR fold provably catches every single-bit
    flip (exhaustive over bit positions + a seeded random sweep standing in
    for hypothesis, which the image does not ship); flips in the payload,
    the count word and the checksum word itself all mismatch.
  * **Injection** — the seeded FaultPlan fires the scheduled fault at the
    scheduled cut/visit/attempt and nowhere else; REPRO_CHAOS parsing.
  * **Policy** — classification routes each failure kind down its own
    recovery path: transient -> backoff retry, corrupt -> wide-format
    re-run, overflow -> escalation ladder, deterministic -> raise on
    attempt 1.  The chaos differential sweep proves recovery is
    byte-identical to the fault-free run on both planner legs (subset in
    the fast lane; all 22 queries under the REPRO_CHAOS CI leg).
  * **Lineage** — exchange snapshots resume the plan suffix; config legs
    and CRC damage invalidate snapshots instead of poisoning results.
"""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import backend as B
from repro.core import wire as W
from repro.core.compat import make_mesh
from repro.data import tpch
from repro.distributed import checkpoint as ckpt
from repro.distributed.chaos import (ChaosInjector, FailureKind, FaultPlan,
                                     FaultSpec, TransientFault,
                                     chaos_env_seed)
from repro.distributed.fault import (QueryRunner, RetryPolicy,
                                     classify_failure, skew_imbalance)
from repro.distributed.lineage import LineageStore, run_resumable
from repro.queries import QUERIES


@pytest.fixture(scope="module")
def db():
    return tpch.generate(0.005, seed=11)


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh((1,), ("data",))


# ---------------------------------------------------------------------------
# wire integrity checksum
# ---------------------------------------------------------------------------

def _block(rng, rows=17, words=3):
    return jnp.asarray(rng.integers(-2**31, 2**31, (rows, words),
                                    dtype=np.int64).astype(np.int32))


def _flip(buf, flat_word, bit):
    flat = np.asarray(buf).reshape(-1).copy()
    flat.view(np.uint32)[flat_word] ^= np.uint32(1 << bit)
    return jnp.asarray(flat.reshape(buf.shape))


def test_checksum_single_bit_flip_exhaustive():
    """EVERY single-bit flip of a small payload changes the checksum — the
    position-rotation makes this a certainty, not a probability."""
    rng = np.random.default_rng(0)
    buf = _block(rng, rows=4, words=2)
    base = int(W.payload_checksum(buf))
    for w in range(8):
        for bit in range(32):
            flipped = int(W.payload_checksum(_flip(buf, w, bit)))
            assert flipped != base, (w, bit)
            # exactly one checksum bit differs
            assert bin(flipped ^ base).count("1") == 1, (w, bit)


def test_checksum_random_bit_flips_always_caught():
    """Property sweep (seeded stand-in for hypothesis): random single-bit
    flips in random payloads are ALWAYS caught by block verification, in
    both header modes, whether they land in the payload, the count word or
    the checksum word."""
    rng = np.random.default_rng(7)
    for trial in range(200):
        rows = int(rng.integers(1, 40))
        words = int(rng.integers(1, 6))
        mode = W.header_mode(words, rows)
        payload = _block(rng, rows=rows, words=words)
        count = jnp.asarray(int(rng.integers(0, rows + 1)), jnp.int32)
        csum = W.payload_checksum(payload)
        hdr = jnp.zeros((words,), jnp.int32) \
            .at[0].set(W.encode_header_word0(count, csum, mode))
        if mode == "word":
            hdr = hdr.at[1].set(W.encode_checksum_word(count, csum))
        assert not bool(W.verify_block_checksum(hdr, payload, mode)), trial
        assert int(W.decode_header_word0(hdr[0], mode)) == int(count)

        blk = jnp.concatenate([hdr[None, :], payload])
        w = int(rng.integers(0, blk.size))
        bit = int(rng.integers(0, 32))
        tampered = _flip(blk, w, bit)
        assert bool(W.verify_block_checksum(tampered[0], tampered[1:],
                                            mode)), (trial, w, bit, mode)


def test_checksum_header_word_flips_detected():
    """Flipping the count or the stored checksum itself must mismatch."""
    rng = np.random.default_rng(3)
    payload = _block(rng, rows=8, words=2)
    count = jnp.asarray(5, jnp.int32)
    csum = W.payload_checksum(payload)
    hdr = jnp.zeros((2,), jnp.int32) \
        .at[0].set(W.encode_header_word0(count, csum, "word")) \
        .at[1].set(W.encode_checksum_word(count, csum))
    for w in range(2):
        for bit in (0, 7, 13, 31):
            blk = _flip(jnp.concatenate([hdr[None, :], payload]), w, bit)
            assert bool(W.verify_block_checksum(blk[0], blk[1:], "word"))


def test_header_mode_static_decision():
    assert W.header_mode(2, 10) == "word"
    assert W.header_mode(7, 1 << 20) == "word"    # word 1 is free
    assert W.header_mode(1, 100) == "folded"
    assert W.header_mode(1, (1 << 16) - 1) == "folded"
    assert W.header_mode(1, 1 << 16) == "none"    # unchecked, statically


def test_folded_mode_roundtrips_count():
    payload = _block(np.random.default_rng(1), rows=9, words=1)
    csum = W.payload_checksum(payload)
    for count in (0, 1, 9, (1 << 16) - 1):
        w0 = W.encode_header_word0(jnp.asarray(count, jnp.int32), csum,
                                   "folded")
        assert int(W.decode_header_word0(w0, "folded")) == count


def test_corrupt_payload_raised_on_distributed_tamper(db, mesh1):
    """A bit flipped in a real packed exchange recv buffer must surface as
    CorruptPayload — never decode into a served result."""
    class OneFlip:
        def fire(self, cut, ctx, tamperable=False):
            if cut == "group_by" and tamperable:
                def tamper(p):
                    u = jax.lax.bitcast_convert_type(
                        p.reshape(-1), jnp.uint32)
                    mid = u.shape[0] // 2
                    u = u.at[mid].set(u[mid] ^ jnp.uint32(1 << 21))
                    return jax.lax.bitcast_convert_type(
                        u, jnp.int32).reshape(p.shape)
                return tamper
            return None

    with pytest.raises(W.CorruptPayload):
        B.run_distributed(QUERIES[13], db, mesh1, capacity_factor=3.0,
                          chaos=OneFlip())


# ---------------------------------------------------------------------------
# injector scheduling
# ---------------------------------------------------------------------------

def test_fault_spec_validation():
    with pytest.raises(ValueError):
        FaultSpec("meteor")
    with pytest.raises(ValueError):
        FaultSpec("transient", cut="join")
    FaultSpec("transient", cut="any")   # ok


def test_chaos_env_parsing(monkeypatch):
    for off in ("", "0", "off", "OFF", "none", "false"):
        monkeypatch.setenv("REPRO_CHAOS", off)
        assert chaos_env_seed() is None
        assert ChaosInjector.from_env() is None
    monkeypatch.delenv("REPRO_CHAOS")
    assert chaos_env_seed() is None
    monkeypatch.setenv("REPRO_CHAOS", "42")
    assert chaos_env_seed() == 42
    inj = ChaosInjector.from_env()
    assert inj.plan == FaultPlan.default(42)


def test_injector_fires_at_scheduled_visit_only():
    class Ctx:
        overflow = jnp.asarray(False)
        corrupt = jnp.asarray(False)

    inj = ChaosInjector(FaultPlan(1, (
        FaultSpec("transient", cut="exchange", index=2, attempt=3),)))
    for attempt in (1, 2):
        inj.begin_attempt(attempt)
        for _ in range(5):
            assert inj.fire("exchange", Ctx()) is None
    inj.begin_attempt(3)
    assert inj.fire("exchange", Ctx()) is None       # visit 0
    assert inj.fire("scan", Ctx()) is None           # other cut: no advance
    assert inj.fire("exchange", Ctx()) is None       # visit 1
    with pytest.raises(TransientFault):
        inj.fire("exchange", Ctx())                  # visit 2: fires
    assert [e.attempt for e in inj.events] == [3]


def test_injector_any_cut_matches_first_visit():
    class Ctx:
        overflow = jnp.asarray(False)
        corrupt = jnp.asarray(False)

    inj = ChaosInjector(FaultPlan(1, (
        FaultSpec("overflow", cut="any", index=0, attempt=1),)))
    ctx = Ctx()
    inj.fire("finalize", ctx)        # whatever cut comes first
    assert bool(ctx.overflow)
    assert inj.events[0].kind == "overflow"


def test_injector_deterministic_tamper_bit():
    """Same (seed, cut, visit, attempt) -> same flipped bit; different seed
    -> (almost surely) a different one."""
    a = ChaosInjector(FaultPlan(1, (FaultSpec("corrupt", cut="exchange"),)))
    b = ChaosInjector(FaultPlan(1, (FaultSpec("corrupt", cut="exchange"),)))
    c = ChaosInjector(FaultPlan(2, (FaultSpec("corrupt", cut="exchange"),)))
    buf = jnp.zeros((8, 4), jnp.int32)

    class Ctx:
        distributed = True
        overflow = jnp.asarray(False)
        corrupt = jnp.asarray(False)

    ta = a.fire("exchange", Ctx(), tamperable=True)
    tb = b.fire("exchange", Ctx(), tamperable=True)
    tc = c.fire("exchange", Ctx(), tamperable=True)
    assert np.array_equal(np.asarray(ta(buf)), np.asarray(tb(buf)))
    assert not np.array_equal(np.asarray(ta(buf)), np.asarray(tc(buf)))
    # exactly one bit differs from the original
    diff = np.asarray(ta(buf)).view(np.uint32) ^ np.asarray(buf).view(np.uint32)
    assert sum(bin(int(x)).count("1") for x in diff.reshape(-1)) == 1


# ---------------------------------------------------------------------------
# failure taxonomy + retry policy
# ---------------------------------------------------------------------------

def test_classification_table():
    assert classify_failure(W.CorruptPayload("x")) is FailureKind.CORRUPT
    for exc in (TypeError("t"), ValueError("v"), KeyError("k"),
                IndexError("i"), AttributeError("a"), AssertionError("s"),
                NameError("n"), ZeroDivisionError("z"),
                jax.errors.JaxRuntimeError(
                    "INTERNAL: Mosaic failed to compile TPU kernel: failed "
                    "to legalize operation 'func.return'"),
                jax.errors.JaxRuntimeError(
                    "RESOURCE_EXHAUSTED: Error allocating device buffer: "
                    "Attempting to allocate 18.00G. That was not possible.")):
        assert classify_failure(exc) is FailureKind.DETERMINISTIC, exc
    for exc in (TransientFault("gone"), OSError("io"), TimeoutError("slow"),
                RuntimeError("unknown"),
                jax.errors.JaxRuntimeError("UNAVAILABLE: link down"),
                jax.errors.JaxRuntimeError(
                    "INTERNAL: Core halted unexpectedly")):
        assert classify_failure(exc) is FailureKind.TRANSIENT, exc


def test_retry_policy_backoff_bounded():
    p = RetryPolicy(backoff_s=0.1, backoff_mult=2.0, max_backoff_s=0.5)
    assert p.backoff(1) == pytest.approx(0.1)
    assert p.backoff(2) == pytest.approx(0.2)
    assert p.backoff(4) == pytest.approx(0.5)    # capped
    assert p.backoff(10) == pytest.approx(0.5)


def test_deterministic_error_raises_on_attempt_1(db, mesh1):
    """The old catch-all burned max_attempts re-executions on plan bugs."""
    inj = ChaosInjector(FaultPlan(1, (
        FaultSpec("deterministic", cut="scan", attempt=1),)))
    runner = QueryRunner(db, mesh1, capacity_factor=3.0, max_attempts=6,
                         chaos=inj)
    with pytest.raises(ValueError, match="plan bug"):
        runner.run(QUERIES[6])
    assert len(inj.events) == 1           # exactly one execution started
    assert runner.chaos.events[0].kind == "deterministic"


def test_corrupt_forces_wide_rerun(db, mesh1):
    inj = ChaosInjector(FaultPlan(9, (
        FaultSpec("corrupt", cut="group_by", attempt=1),)))
    runner = QueryRunner(db, mesh1, capacity_factor=3.0, max_attempts=4,
                         wire_format="narrow", chaos=inj,
                         policy=RetryPolicy(max_attempts=4, backoff_s=0.01))
    res = runner.run(QUERIES[13])
    rows = res.report.rows()
    assert [r["outcome"] for r in rows] == ["corrupt", "ok"]
    assert rows[0]["wire_format"] == "narrow"
    assert rows[1]["wire_format"] == "wide"     # never trust the bad buffer
    assert rows[0]["cut"] == "group_by"


def test_transient_retries_with_backoff(db, mesh1):
    inj = ChaosInjector(FaultPlan(4, (
        FaultSpec("transient", cut="scan", attempt=1),
        FaultSpec("transient", cut="scan", attempt=2),)))
    runner = QueryRunner(db, mesh1, capacity_factor=3.0, chaos=inj,
                         policy=RetryPolicy(max_attempts=4, backoff_s=0.01,
                                            backoff_mult=3.0))
    res = runner.run(QUERIES[6])
    rows = res.report.rows()
    assert [r["outcome"] for r in rows] == ["transient", "transient", "ok"]
    assert rows[0]["backoff_s"] == pytest.approx(0.01)
    assert rows[1]["backoff_s"] == pytest.approx(0.03)   # exponential
    assert res.attempts == 3


def test_transient_exhaustion_reraises(db, mesh1):
    inj = ChaosInjector(FaultPlan(4, tuple(
        FaultSpec("transient", cut="scan", attempt=a) for a in (1, 2))))
    runner = QueryRunner(db, mesh1, capacity_factor=3.0, chaos=inj,
                         policy=RetryPolicy(max_attempts=2, backoff_s=0.01))
    with pytest.raises(TransientFault):
        runner.run(QUERIES[6])


def _sweep_qids():
    """Fast-lane subset; the REPRO_CHAOS CI leg widens to all 22."""
    return sorted(QUERIES) if chaos_env_seed() is not None else [1, 6, 9, 13]


@pytest.mark.parametrize("infer", [True, False])
def test_chaos_differential_sweep(db, mesh1, infer):
    """The acceptance sweep: under the default seeded FaultPlan (one
    transient + one corrupt + one overflow) every query recovers to a
    result byte-identical to the fault-free run, on both planner legs, and
    the RunReport classifies every injected fault correctly."""
    for qid in _sweep_qids():
        q = QUERIES[qid].with_inference(infer)
        clean, _, ov = B.run_distributed(q, db, mesh1, capacity_factor=3.0)
        assert not ov, qid
        # start at 1.5 so the injected overflow escalates to exactly the
        # clean run's factor -- byte-identity is then apples-to-apples
        runner = QueryRunner(db, mesh1, capacity_factor=1.5, escalation=2.0,
                             chaos=ChaosInjector(FaultPlan.default(11)),
                             policy=RetryPolicy(max_attempts=6,
                                                backoff_s=0.01))
        res = runner.run(q)
        outcomes = res.report.outcomes()
        assert outcomes[:3] == ["transient", "corrupt", "overflow"], (
            qid, infer, outcomes)
        assert outcomes[-1] == "ok", (qid, infer, outcomes)
        kinds = [f.kind for f in res.report.injected]
        assert kinds == ["transient", "corrupt", "overflow"], (qid, kinds)
        assert set(clean) == set(res.result), qid
        for k in clean:
            np.testing.assert_array_equal(
                np.asarray(clean[k]), np.asarray(res.result[k]),
                err_msg=f"q{qid} {k} infer={infer}")


# ---------------------------------------------------------------------------
# skew_imbalance satellite
# ---------------------------------------------------------------------------

def test_skew_imbalance_validates_shape():
    with pytest.raises(ValueError, match="not divisible"):
        skew_imbalance(np.arange(10), k=4)
    with pytest.raises(ValueError, match="k must be"):
        skew_imbalance(np.arange(8), k=0)


def test_skew_imbalance_edges_return_neutral():
    assert skew_imbalance(np.array([]), k=1) == 1.0
    assert skew_imbalance(np.array([37]), k=1) == 1.0        # single node
    assert skew_imbalance(np.array([1, 2, 3, 4]), k=4) == 1.0
    assert skew_imbalance(np.zeros(8, np.int64), k=1) == 1.0  # no traffic


def test_skew_imbalance_values_preserved():
    counts = np.array([40, 10, 10, 10, 20, 10, 10, 10])
    assert skew_imbalance(counts, k=1) == pytest.approx(40 / 15)   # max/mean
    assert skew_imbalance(counts, k=4) == pytest.approx(70 / 60)   # [70, 50]


# ---------------------------------------------------------------------------
# lineage snapshots
# ---------------------------------------------------------------------------

def test_restore_flat_roundtrip(tmp_path):
    flat = {"a": np.arange(5), "b": np.float64(2.5).reshape(()),
            "z": np.ones((2, 3), np.int32)}
    ckpt.save(str(tmp_path), 3, flat,
              metadata={"keys": sorted(flat), "config": {"leg": 1}})
    got, meta = ckpt.restore_flat(str(tmp_path), 3)
    assert meta["config"] == {"leg": 1}
    assert sorted(got) == sorted(flat)
    for k in flat:
        np.testing.assert_array_equal(np.asarray(got[k]), flat[k])


def test_restore_flat_rejects_non_flat(tmp_path):
    ckpt.save(str(tmp_path), 0, {"a": np.arange(3)})    # no keys metadata
    with pytest.raises(ValueError, match="keys"):
        ckpt.restore_flat(str(tmp_path), 0)


def test_restore_flat_checksum(tmp_path):
    ckpt.save(str(tmp_path), 1, {"a": np.arange(64)},
              metadata={"keys": ["a"]})
    target = tmp_path / "step_0000000001" / "000000.npy"
    raw = bytearray(target.read_bytes())
    raw[-3] ^= 0x10
    target.write_bytes(bytes(raw))
    with pytest.raises(IOError, match="checksum"):
        ckpt.restore_flat(str(tmp_path), 1)


def test_lineage_resume_skips_subtree(db, tmp_path):
    """Fail at finalize -> every exchange is durable -> the retry restores
    the topmost snapshot and re-executes only the suffix (its PlanStats
    show no exchanges re-issued)."""
    q = QUERIES[9]
    store = LineageStore(str(tmp_path / "lin"))
    inj = ChaosInjector(FaultPlan(3, (
        FaultSpec("transient", cut="finalize", attempt=1),)))
    with pytest.raises(TransientFault):
        run_resumable(q, db, store, capacity_factor=3.0, chaos=inj)
    assert store.saved >= 1
    inj.begin_attempt(2)
    r, stats, ov, reused = run_resumable(q, db, store, capacity_factor=3.0,
                                         chaos=inj)
    assert not ov and reused >= 1
    assert stats.shuffles == 0 and stats.broadcasts == 0   # subtree skipped
    r_ref, _ = B.run_reference(q, db)
    for k in set(r_ref) & set(r):
        np.testing.assert_allclose(np.asarray(r[k], np.float64),
                                   np.asarray(r_ref[k], np.float64),
                                   rtol=1e-7, err_msg=k)


def test_lineage_config_leg_invalidates(db, tmp_path):
    """A snapshot written on the narrow/inference leg must NOT be served to
    a wide or hint-dropped re-run."""
    q = QUERIES[9]
    store = LineageStore(str(tmp_path / "lin"))
    run_resumable(q, db, store, capacity_factor=3.0, wire_format="narrow")
    assert store.saved >= 1
    r, _, ov, reused = run_resumable(q, db, store, capacity_factor=3.0,
                                     wire_format="wide")
    assert reused == 0 and not ov
    r2, _, ov2, reused2 = run_resumable(q.with_inference(False), db, store,
                                        capacity_factor=3.0,
                                        wire_format="narrow")
    assert reused2 == 0 and not ov2


def test_lineage_torn_snapshot_falls_back(db, tmp_path):
    """CRC damage to a snapshot file -> silent fall back to re-execution,
    never a poisoned resume."""
    q = QUERIES[9]
    store = LineageStore(str(tmp_path / "lin"))
    r1, _, _, _ = run_resumable(q, db, store, capacity_factor=3.0)
    # corrupt every snapshot's first leaf
    for step in sorted(os.listdir(store.dir)):
        leaf = os.path.join(store.dir, step, "000000.npy")
        with open(leaf, "r+b") as f:
            f.seek(-2, 2)
            b = f.read(1)
            f.seek(-2, 2)
            f.write(bytes([b[0] ^ 0xFF]))
    r2, _, ov, reused = run_resumable(q, db, store, capacity_factor=3.0)
    assert reused == 0 and not ov
    for k in r1:
        np.testing.assert_array_equal(np.asarray(r1[k]), np.asarray(r2[k]))


def test_lineage_noop_under_jit(db, tmp_path):
    """Under jit the values are Tracers: snapshots must be skipped, not
    crash the trace."""
    store = LineageStore(str(tmp_path / "lin"))

    def q(ctx):
        ctx.lineage = store
        return QUERIES[1](ctx)

    r, _ = B.run_local(q, db, jit=True)
    assert store.saved == 0 and store.reused == 0
    r_ref, _ = B.run_reference(QUERIES[1], db)
    np.testing.assert_allclose(
        np.asarray(r["sum_qty"], np.float64),
        np.asarray(r_ref["sum_qty"], np.float64), rtol=1e-7)


# ---------------------------------------------------------------------------
# report surfacing
# ---------------------------------------------------------------------------

def test_run_report_rendered(db, mesh1, capsys):
    from repro.launch import report as rep
    runner = QueryRunner(db, mesh1, capacity_factor=3.0,
                         chaos=ChaosInjector(FaultPlan.default(2)),
                         policy=RetryPolicy(max_attempts=6, backoff_s=0.01))
    res = runner.run(QUERIES[1])
    rec = rep.run_report_record("q1", res.report)
    rec = json.loads(json.dumps(rec))      # must be JSON-able
    rep.run_report_table([rec])
    out = capsys.readouterr().out
    assert "| q1 | 1 | transient | scan |" in out
    assert "| q1 | 2 | corrupt | group_by |" in out
    assert out.strip().splitlines()[-1].split("|")[3].strip() == "ok"
