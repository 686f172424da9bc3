"""Recovery benchmark: lineage resume vs whole-query re-execution.

The paper's fault story is re-execution from durable inputs (§2.4): a failed
query costs a full second pass.  The lineage subsystem
(``repro.distributed.lineage``) snapshots every post-exchange table through
the CRC-checksummed checkpoint writer, so a query that dies AFTER its
exchanges (the common case — finalize, result fetch, a straggler timeout on
the last collective) resumes from the topmost durable exchange and re-executes
only the plan suffix.

This benchmark measures that payoff end-to-end, per query:

  * ``full_s``    — warm eager re-execution of the whole query (the paper's
                    recovery cost; no lineage armed).
  * ``resume_s``  — warm resume from a populated lineage store: restore the
                    topmost snapshot (CRC-verified) + re-execute the suffix.
  * ``reshard_s`` — warm resume at a SHRUNKEN topology (snapshots written
                    for an 8-wide mesh, resumed at 5): the degraded-mesh
                    path, which adopts width-mismatched snapshots through
                    the store's re-shard rule instead of recomputing from
                    scan.  Gated against full re-execution by
                    ``MAX_RESHARD_RATIO``.

Timings are min-over-``--reps`` after a warm-up pass, so JIT/trace cost and
page-cache effects hit both legs equally.  The store is populated once by a
run that simulates the fault at ``finalize`` — the snapshots a real failed
attempt would have left behind.

    PYTHONPATH=src python benchmarks/bench_recovery.py [--check] [--sf 0.05]

Writes ``BENCH_recovery.json`` at the repo root.  ``--check`` exits non-zero
unless every gated query resumes in < ``MAX_RECOVERY_RATIO`` x its full
re-execution wall — bounded recovery, CI-gateable on CPU with no cluster.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time

from repro.core import backend as B
from repro.data import tpch
from repro.distributed.chaos import ChaosInjector, FaultPlan, FaultSpec, \
    TransientFault
from repro.distributed.lineage import LineageStore, run_resumable
from repro.queries import QUERIES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(ROOT, "BENCH_recovery.json")

# Resume must cost less than this fraction of a full re-execution.  The
# gated queries have deep exchange trees (joins feeding a group_by), so the
# suffix after the topmost exchange is a small tail of the plan; snapshot
# restore is CRC + npy I/O on a compacted table.
MAX_RECOVERY_RATIO = 0.6

# A degraded-mesh resume (snapshots written at width N, adopted at N') pays
# the same restore + suffix as a same-width resume — eager snapshots are
# stored in global row order, so no data movement is added — but gets its
# own, slightly looser budget so the gate localizes a regression in the
# re-shard rule itself.
MAX_RESHARD_RATIO = 0.7
RESHARD_FROM, RESHARD_TO = 8, 5

# Queries the ratio gate applies to at the default --sf.  Every query is
# still measured and reported.
RECOVERY_QUERIES = (5, 9, 18)


def _time(fn, reps: int) -> float:
    fn()                                  # warm-up: traces, page cache
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--queries", type=int, nargs="*", default=None,
                    help="query ids to measure (default: the gated set)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless every gated query resumes in"
                         " < MAX_RECOVERY_RATIO x full re-execution")
    args = ap.parse_args()
    qids = args.queries if args.queries else sorted(RECOVERY_QUERIES)

    db = tpch.generate(args.sf, seed=args.seed)
    report = {"sf": args.sf, "seed": args.seed, "reps": args.reps,
              "max_recovery_ratio": MAX_RECOVERY_RATIO,
              "max_reshard_ratio": MAX_RESHARD_RATIO,
              "reshard_widths": [RESHARD_FROM, RESHARD_TO],
              "gated_queries": sorted(RECOVERY_QUERIES), "queries": {}}
    ok = True
    work = tempfile.mkdtemp(prefix="bench_recovery_")
    try:
        for qid in qids:
            q = QUERIES[qid]
            store = LineageStore(os.path.join(work, f"q{qid}"))

            # populate: the snapshots a mid-query failure leaves behind
            # (fault fires at finalize -> every exchange already durable)
            inj = ChaosInjector(FaultPlan(qid, (
                FaultSpec("transient", cut="finalize", attempt=1),)))
            try:
                run_resumable(q, db, store, capacity_factor=3.0, chaos=inj)
            except TransientFault:
                pass
            snapshots = store.saved       # before resumes reset the counter
            assert snapshots >= 1, f"q{qid}: no exchange snapshots written"

            full_s = _time(
                lambda: B.run_local(q, db, jit=False, capacity_factor=3.0),
                args.reps)

            def resume():
                _, _, _, reused = run_resumable(q, db, store,
                                                capacity_factor=3.0)
                assert reused >= 1, f"q{qid}: resume did not hit a snapshot"
            resume_s = _time(resume, args.reps)

            # degraded-mesh leg: snapshots written for an 8-wide topology,
            # adopted by a 5-wide resume through the width-only-mismatch
            # re-shard rule (LineageStore.resharded counts the adoptions)
            store_w = LineageStore(os.path.join(work, f"q{qid}_w"))
            inj_w = ChaosInjector(FaultPlan(qid, (
                FaultSpec("transient", cut="finalize", attempt=1),)))
            try:
                run_resumable(q, db, store_w, capacity_factor=3.0,
                              chaos=inj_w, n_devices=RESHARD_FROM)
            except TransientFault:
                pass

            def reshard_resume():
                _, _, _, reused = run_resumable(q, db, store_w,
                                                capacity_factor=3.0,
                                                n_devices=RESHARD_TO)
                assert reused >= 1, f"q{qid}: re-shard resume missed"
                assert store_w.resharded >= 1, \
                    f"q{qid}: resume did not exercise the re-shard path"
            reshard_s = _time(reshard_resume, args.reps)

            ratio = resume_s / full_s
            reshard_ratio = reshard_s / full_s
            gated = qid in RECOVERY_QUERIES
            q_ok = (not gated) or (ratio < MAX_RECOVERY_RATIO
                                   and reshard_ratio < MAX_RESHARD_RATIO)
            ok &= q_ok
            report["queries"][f"q{qid}"] = {
                "full_s": round(full_s, 4), "resume_s": round(resume_s, 4),
                "ratio": round(ratio, 3),
                "reshard_s": round(reshard_s, 4),
                "reshard_ratio": round(reshard_ratio, 3),
                "snapshots": snapshots,
                "gated": gated,
            }
            flag = "" if q_ok else "  ** OVER RATIO **"
            print(f"q{qid:2d}: full {full_s * 1e3:7.1f}ms -> resume "
                  f"{resume_s * 1e3:7.1f}ms  (ratio {ratio:.2f}) -> reshard "
                  f"{RESHARD_FROM}->{RESHARD_TO} {reshard_s * 1e3:7.1f}ms "
                  f"(ratio {reshard_ratio:.2f}, "
                  f"{snapshots} snapshots){flag}", flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    report["pass"] = bool(ok)
    with open(OUT_PATH, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {OUT_PATH}  pass={ok}")
    if args.check and not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro import use_compile_cache
    use_compile_cache()
    main()
