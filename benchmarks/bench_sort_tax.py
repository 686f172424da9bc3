"""Sort-tax benchmark: HLO ``sort`` op counts + wall clock for representative
TPC-H local plans (Q1 scan-heavy, Q3 join+topk, Q6 pure scan, Q9 multi-join,
Q12 join+small-domain group), vs the seed engine's numbers.

The seed engine paid an O(cap log cap) argsort in every filter (compaction),
every join (build re-sort) and one argsort per ORDER BY key; phase 1 removed
most of it (deferred compaction / single-sort operators / build cache) and
phase 2 removed the rest of the hot-path sorts (direct-addressing group-bys
via ``key_bits``, counting-rank shuffle dispatch).  This benchmark guards
both phases against regression.  Run:

    PYTHONPATH=src python benchmarks/bench_sort_tax.py [--check] [--sf 0.01]

Writes ``BENCH_sort_tax.json`` at the repo root.  ``--check`` exits non-zero
unless every query's HLO sort count is within its ABSOLUTE budget
(``MAX_SORT_OPS`` — the phase-2 gate) and, where a true seed measurement
exists, down >= 40% vs the seed (the phase-1 gate).  Phase 3 (the logical
planner): queries compile through the builder+planner path with inference
pinned on, and the report additionally records the planner's own cost per
query (``plan_build_ms`` / ``plan_infer_ms`` — DAG construction and bound
propagation, both host-side and cached per database in production use).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.core import backend as B
from repro.core import planner as PL
from repro.core import relational as rel
from repro.core.table import Table
from repro.data import tpch
from repro.distributed.hlo_analysis import op_histogram
from repro.queries import PLANS, QUERIES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(ROOT, "BENCH_sort_tax.json")

BENCH_QUERIES = (1, 3, 6, 9, 12, 13)

# Seed-engine numbers, measured at sf=0.01 seed=7 on the pre-optimization
# commit (eager compaction, per-key sort passes, per-join build sorts) with
# the same best-of-9 protocol used below.  q6/q12 were added for phase 2 and
# have no true seed measurement; their baseline is the phase-1 engine
# (PR 1: deferred compaction + single-sort operators + build cache).  q13 was
# added for the hash-compaction path: its baseline is the phase-3 engine,
# where the data-dependent c_count group-by still paid the single-sort path.
SEED_BASELINE = {
    "q1": {"sort_ops": 4, "wall_ms": 81.3},
    "q3": {"sort_ops": 10, "wall_ms": 140.0},
    "q9": {"sort_ops": 12, "wall_ms": 142.0},
    "q6": {"sort_ops": 1, "wall_ms": 19.5, "phase1": True},
    "q12": {"sort_ops": 3, "wall_ms": 35.1, "phase1": True},
    "q13": {"sort_ops": 3, "wall_ms": 8.4, "phase1": True},
}

MIN_SORT_DROP = 0.40

# Phase-2 absolute budgets (hinted group-bys sortless, dispatch sortless;
# q13's group-by stage sortless via the hash-compaction dictionary; joins on
# a proven dense build key index by direct addressing, with no sort);
# keep in sync with tests/test_sort_tax.py::_MAX_SORTS, except q3, whose
# l_orderkey group-by takes the sort path at this bench's sf 0.01.
MAX_SORT_OPS = {"q1": 1, "q3": 2, "q6": 0, "q9": 2, "q12": 1, "q13": 1}


def _plan_times(db, qid: int, iters: int = 9) -> tuple[float, float]:
    """(plan build ms, planner inference ms): the cost of the logical layer.

    Build = constructing the plan DAG from the builder; inference = bound
    propagation + hint derivation + placement validation (host-side, cached
    per database in production use — measured uncached here).
    """
    build_ts, infer_ts = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        root = PLANS[qid]()
        build_ts.append(time.perf_counter() - t0)
        PL.invalidate_stats(db)                       # measure cold inference
        t0 = time.perf_counter()
        PL.analyze(root, db)
        infer_ts.append(time.perf_counter() - t0)
    return min(build_ts) * 1e3, min(infer_ts) * 1e3


def _compile_and_time(db, tables, qid: int, join_method: str,
                      iters: int = 9):
    def run(tables):
        ctx = B.LocalContext(db, tables, join_method=join_method)
        # inference pinned ON: the gate measures the compiled planner path
        # regardless of the REPRO_PLANNER leg running the bench
        out = QUERIES[qid].run(ctx, infer=True)
        if isinstance(out, dict):
            out = Table({k: jnp.asarray(v).reshape(1) for k, v in out.items()},
                        jnp.asarray(1, jnp.int32))
        return rel.ensure_compact(out), ctx.overflow

    fn = jax.jit(run)
    compiled = fn.lower(tables).compile()
    nsort = op_histogram(compiled.as_text(), ops=("sort",))["sort"]
    jax.block_until_ready(fn(tables))          # warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(tables))
        ts.append(time.perf_counter() - t0)
    # best-of-N: the engines are deterministic, so min suppresses scheduler
    # noise that medians on a shared host do not
    return nsort, min(ts) * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless every query meets its absolute"
                         " sort budget (and >= 40%% drop vs a true seed)")
    args = ap.parse_args()

    db = tpch.generate(args.sf, seed=args.seed)
    tables = B._np_db_to_tables(db)

    report = {"sf": args.sf, "seed_baseline": SEED_BASELINE, "queries": {}}
    ok = True
    for qid in BENCH_QUERIES:
        nsort, wall_ms = _compile_and_time(db, tables, qid, "sorted")
        _, wall_hash = _compile_and_time(db, tables, qid, "hash")
        build_ms, infer_ms = _plan_times(db, qid)
        seed = SEED_BASELINE[f"q{qid}"]
        budget = MAX_SORT_OPS[f"q{qid}"]
        drop = 1.0 - nsort / seed["sort_ops"]
        speedup = seed["wall_ms"] / wall_ms
        report["queries"][f"q{qid}"] = {
            "sort_ops": nsort,
            "max_sort_ops": budget,
            "seed_sort_ops": seed["sort_ops"],
            "sort_drop": round(drop, 3),
            "wall_ms": round(wall_ms, 2),
            "wall_ms_hash_join": round(wall_hash, 2),
            "seed_wall_ms": seed["wall_ms"],
            "speedup_vs_seed": round(speedup, 2),
            "plan_build_ms": round(build_ms, 3),
            "plan_infer_ms": round(infer_ms, 3),
        }
        ok &= nsort <= budget
        if not seed.get("phase1"):      # the 40% rule needs a true seed
            ok &= drop >= MIN_SORT_DROP
        print(f"q{qid}: sorts {seed['sort_ops']} -> {nsort} "
              f"({drop:.0%} drop, budget {budget}), wall {seed['wall_ms']:.1f}"
              f" -> {wall_ms:.1f} ms ({speedup:.2f}x)"
              f"  [hash-join {wall_hash:.1f} ms,"
              f" plan build {build_ms:.2f} ms + infer {infer_ms:.2f} ms]",
              flush=True)

    report["min_sort_drop"] = MIN_SORT_DROP
    report["max_sort_ops"] = MAX_SORT_OPS
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
