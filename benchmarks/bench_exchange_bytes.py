"""Wire-byte benchmark: per-query exchange payload widths, derived from the
logical-plan IR with NO execution (``planner.static_wire_stats``).

The paper's Hockney model (§3.6) makes interconnect bytes-per-row the
dominant distributed term; the stats-driven narrow wire format
(``core/wire.py``) ships every exchanged column at its inferred lane width.
This benchmark derives, for each of the 22 query plans, the summed per-row
wire bytes of every exchange (shuffle / broadcast / final gather) in the
narrow format vs the legacy wide format — numbers that are asserted equal to
runtime ``ExchangeStats`` on all three backends (tests/test_wire.py), so the
win is CI-gateable on CPU with no cluster, exactly like the sort-tax gates.

    PYTHONPATH=src python benchmarks/bench_exchange_bytes.py [--check] [--sf 0.01]

Writes ``BENCH_exchange_bytes.json`` at the repo root.  ``--check`` exits
non-zero unless every query's narrow wire bytes are within its ABSOLUTE
budget (``MAX_WIRE_BYTES``) and the shuffle-heavy queries show at least a
40% reduction vs wide (``MIN_WIRE_DROP_QUERIES``).
"""
from __future__ import annotations

import argparse
import json
import os

from repro.core import planner as PL
from repro.data import tpch
from repro.queries import QUERIES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(ROOT, "BENCH_exchange_bytes.json")

# Absolute per-query budgets: summed narrow row-wire bytes across every
# exchange of the plan, measured at sf=0.01 seed=7 (bounds are column
# statistics of the generated database, stable per (sf, seed)).  Keep in
# sync with the narrow layout — a widened lane shows up here immediately.
MAX_WIRE_BYTES = {
    1: 92, 2: 28, 3: 16, 4: 12, 5: 20, 6: 0, 7: 20, 8: 32, 9: 44, 10: 32,
    11: 16, 12: 20, 13: 28, 14: 20, 15: 24, 16: 24, 17: 16, 18: 48, 19: 4,
    20: 16, 21: 16, 22: 32,
}

# Shuffle-heavy plans (ISSUE 4 acceptance): narrow must cut >= 40% of the
# wide format's wire bytes.  Integer arithmetic: (wide - narrow) / wide.
MIN_WIRE_DROP = 0.40
MIN_WIRE_DROP_QUERIES = (5, 7, 8, 9, 18)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless every query meets its absolute"
                         " wire-byte budget (and the shuffle-heavy set drops"
                         " >= 40%% vs the wide format)")
    args = ap.parse_args()

    db = tpch.generate(args.sf, seed=args.seed)
    report = {"sf": args.sf, "seed": args.seed, "queries": {},
              "max_wire_bytes": MAX_WIRE_BYTES,
              "min_wire_drop": MIN_WIRE_DROP,
              "min_wire_drop_queries": list(MIN_WIRE_DROP_QUERIES)}
    ok = True
    for qid in sorted(QUERIES):
        narrow = QUERIES[qid].static_wire(db, narrow=True)
        wide = QUERIES[qid].static_wire(db, narrow=False)
        nb = sum(e["row_wire_bytes"] for e in narrow)
        wb = sum(e["row_wire_bytes"] for e in wide)
        lb = sum(e["row_logical_bytes"] for e in narrow)
        drop = 0.0 if wb == 0 else 1.0 - nb / wb
        budget = MAX_WIRE_BYTES[qid]
        q_ok = nb <= budget
        # integer form of the >= 40% rule (no float edge at exactly 40%)
        if qid in MIN_WIRE_DROP_QUERIES:
            q_ok &= (wb - nb) * 100 >= int(MIN_WIRE_DROP * 100) * wb
        report["queries"][f"q{qid}"] = {
            "wire_bytes_narrow": nb,
            "wire_bytes_wide": wb,
            "logical_bytes": lb,
            "max_wire_bytes": budget,
            "reduction": round(drop, 3),
            "exchanges": [
                {"kind": n["kind"], "narrow": n["row_wire_bytes"],
                 "wide": w["row_wire_bytes"],
                 "logical": n["row_logical_bytes"]}
                for n, w in zip(narrow, wide)],
        }
        ok &= q_ok
        flag = "" if q_ok else "  ** OVER BUDGET **"
        print(f"q{qid:2d}: wire {wb:3d} -> {nb:3d} bytes/row "
              f"({drop:.0%} drop, budget {budget}){flag}", flush=True)

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
