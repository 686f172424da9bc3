"""Sample-ladder benchmark: per-rung wall clock vs CI width for q1/q6/q18.

For each query the exact plan and every ladder rung (1/16..1/1) are compiled
once into a standing jitted executable (rung construction and compilation are
amortized, exactly as ``QueryServer`` amortizes them); the reported wall is
min-over-``--reps`` of the compiled call.  Each rung also reports the max
relative CI half-width ``repro.approx.estimators`` attaches to its answer —
the two axes of the accuracy/latency trade the progressive runner walks.

q18 is the deliberate odd one out: its grouped ``sum_qty`` feeds a
HAVING-style filter and two joins, so group membership would be decided by
un-barred estimates — the rewrite refuses every sampled rung (recorded as
``"refused": true``) and only the rename-only top rung runs.  The gate pins
that refusal: an estimability regression that starts sampling q18 again
fails the bench, because the last time that happened the scaled answer was
served with a fabricated zero CI.

    PYTHONPATH=src python benchmarks/bench_approx.py [--check] [--sf 0.05]

Writes ``BENCH_approx.json`` at the repo root.  ``--check`` exits non-zero
unless, for every query:

  * the top rung (den == 1) is byte-identical to the exact plan — the
    differential identity the rewrite guarantees by construction;
  * refusal is shape-based and therefore total: either every sampled rung
    refused (q18) or none did (q1/q6);
  * for measured ladders, CI width is non-increasing as the sample grows
    (inf sorts above everything; the top rung is exactly 0);
  * for measured ladders, wall clock is monotone across the sampled rungs
    (1/16..1/2) within a noise allowance, and the smallest rung is
    measurably below the exact wall — the whole point of answering from a
    sample.  The top rung is excluded from the wall gate: sampled rungs pay
    for the CLT moment aggregates the rename-only top rung drops, so a
    half-sample plan may legitimately cost as much as the exact one.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import time

import jax
import jax.numpy as jnp

from repro.core import backend as B
from repro.core import relational as rel
from repro.core.table import Table, to_numpy
from repro.data import tpch
from repro.queries import QUERIES
from repro.approx.rewrite import rewrite_for_rung
from repro.approx.sampling import LADDER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(ROOT, "BENCH_approx.json")

QIDS = (1, 6, 18)
# smallest rung must beat exact by at least this factor; adjacent rungs may
# regress by at most WALL_SLACK (timing noise on small inputs)
SPEEDUP_MIN = 1.25
WALL_SLACK = 1.15


def _executable(query_fn, db, capacity_factor: float = 3.0):
    """One standing jitted executable over the database's device tables."""
    tables = B._np_db_to_tables(db)

    def run(tables):
        ctx = B.LocalContext(db, tables, capacity_factor=capacity_factor)
        out = query_fn(ctx)
        if isinstance(out, dict):
            out = Table({k: jnp.asarray(v).reshape(1) for k, v in out.items()},
                        jnp.asarray(1, jnp.int32))
        return rel.ensure_compact(out), ctx.overflow
    return jax.jit(run), tables


def _time(fn, tables, reps: int):
    out, overflow = fn(tables)          # warm-up (compile) outside the clock
    assert not bool(overflow), "capacity overflow in bench run"
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out, _ = fn(tables)
        jax.block_until_ready(out.columns if hasattr(out, "columns") else out)
        best = min(best, time.perf_counter() - t0)
    return best, to_numpy(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless identity + monotonicity gates "
                         "hold for every query")
    args = ap.parse_args()

    db = tpch.generate(args.sf, seed=args.seed)
    queries, checks = {}, {}
    for qid in QIDS:
        q = QUERIES[qid]
        fn, tables = _executable(q, db)
        exact_wall, exact_cols = _time(fn, tables, args.reps)
        rungs = []
        identical = True
        for den in LADDER:
            rw = rewrite_for_rung(q, db, den)
            if rw is None:
                assert den != 1, f"q{qid}: the rename-only top rung refused"
                rungs.append({"den": den, "refused": True})
                continue
            rfn, rtables = _executable(rw.query, rw.db)
            wall, cols = _time(rfn, rtables, args.reps)
            est = rw.finalize(cols)
            ci = float(est.rel_width)
            rungs.append({"den": den, "wall_s": round(wall, 5),
                          "ci": None if math.isinf(ci) else round(ci, 5)})
            if den == 1:
                identical = set(cols) == set(exact_cols) and all(
                    (cols[k] == exact_cols[k]).all() for k in exact_cols)
        measured = [r for r in rungs if not r.get("refused")]
        refused = len(rungs) - len(measured)
        walls = [r["wall_s"] for r in measured]
        cis = [math.inf if r["ci"] is None else r["ci"] for r in measured]
        checks[f"q{qid}"] = {
            "rung1_byte_identical": bool(identical),
            "refusal_is_total": refused in (0, len(LADDER) - 1),
            "ci_monotone_nonincreasing": all(
                a >= b - 1e-12 for a, b in zip(cis, cis[1:])),
            "top_rung_ci_zero": cis[-1] == 0.0,
        }
        if refused == 0:
            checks[f"q{qid}"].update({
                "wall_monotone_with_slack": all(
                    a <= b * WALL_SLACK
                    for a, b in zip(walls[:-1], walls[1:-1])),
                "smallest_rung_beats_exact":
                    walls[0] * SPEEDUP_MIN <= exact_wall,
            })
        else:
            # the estimability gate, not the latency ladder, is under test:
            # this shape folds grouped estimates into downstream computation
            checks[f"q{qid}"]["sampled_rungs_refuse"] = \
                refused == len(LADDER) - 1
        queries[f"q{qid}"] = {"exact_wall_s": round(exact_wall, 5),
                              "rungs": rungs}
        parts = []
        for r in rungs:
            if r.get("refused"):
                parts.append(f"1/{r['den']} refused")
                continue
            ci_s = "inf" if r["ci"] is None else f"{100 * r['ci']:.2f}%"
            parts.append(f"1/{r['den']} {r['wall_s'] * 1e3:.2f}ms ci={ci_s}")
        print(f"q{qid}: exact {exact_wall * 1e3:.2f}ms | " + " ".join(parts))

    ok = all(all(c.values()) for c in checks.values())
    report = {"sf": args.sf, "seed": args.seed, "reps": args.reps,
              "ladder": list(LADDER), "queries": queries,
              "checks": checks, "pass": bool(ok)}
    with open(OUT_PATH, "w") as f:
        json.dump(report, f, indent=1)
    for qname, c in checks.items():
        for name, passed in c.items():
            if not passed:
                print(f"  FAIL {qname}.{name}")
    print(f"wrote {OUT_PATH}  pass={ok}")
    if args.check and not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro import use_compile_cache
    use_compile_cache()
    main()
