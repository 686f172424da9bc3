"""Serving benchmark: compiled-template throughput vs per-request jit.

Multi-tenant serving (``repro.serve``) amortizes planning and compilation
across requests: each of the 22 TPC-H templates is analyzed once against the
parameter DOMAINS and jit-traced once; a request binds parameter VALUES into
the standing executable as traced scalars.  This benchmark drives a mixed,
interleaved parameterized request stream (every template, every sample
binding) through three execution modes:

  * ``server``   — :class:`repro.serve.QueryServer`: bind + cached
                   executable + device call per request.
  * ``batch``    — :class:`repro.serve.BatchExecutor`: the whole stream as
                   one eager batch with the cross-query subplan memo.
  * ``per_jit``  — the no-serving baseline: ``run_local(jit=True)`` per
                   request, i.e. every request pays trace + compile.

Timings are min-over-``--reps`` of a full stream pass after a warm-up pass
(the server's warm-up pass is also where all compiles happen — reported as
``cold_s``).

    PYTHONPATH=src python benchmarks/bench_serve.py [--check] [--sf 0.05]

Writes ``BENCH_serve.json`` at the repo root.  ``--check`` exits non-zero
unless the recompile count equals the number of DISTINCT TEMPLATES in the
stream — re-binding a parameter must never re-trace; an accidental retrace
(dtype drift, pytree-structure drift, a binding leaking into a cache key)
breaks exactly this invariant, and the counter increments inside the traced
body so no retrace can hide.  The gate also requires cross-query sharing in
batch mode and at least two exercised bindings per parameterized template,
so the stream genuinely covers the serving surface.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro import serve
from repro.core import backend as B
from repro.data import tpch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(ROOT, "BENCH_serve.json")


def _stream():
    """Mixed interleaved parameterized traffic: every sample of all 22
    templates, round-robin so consecutive requests come from different
    templates (the serving-unfriendly order)."""
    per = [[(t, s) for s in t.samples]
           for _, t in sorted(serve.TEMPLATES.items())]
    out, i = [], 0
    while any(per):
        if per[i % len(per)]:
            out.append(per[i % len(per)].pop(0))
        i += 1
    return out


def _time(fn, reps: int) -> float:
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
    ap.add_argument("--baseline", action="store_true",
                    help="also time the per-request-jit baseline (slow: "
                         "every request re-traces)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless recompiles == distinct "
                         "templates (and batch sharing happened)")
    args = ap.parse_args()

    db = tpch.generate(args.sf, seed=args.seed)
    reqs = _stream()
    n_templates = len({id(t) for t, _ in reqs})
    n_param = sum(1 for t in serve.TEMPLATES.values() if t.params)

    srv = serve.QueryServer(db)
    t0 = time.perf_counter()
    srv.serve(reqs, infer=True)          # cold pass: every template compiles
    cold_s = time.perf_counter() - t0
    serve_s = _time(lambda: srv.serve(reqs, infer=True), args.reps)

    bx = serve.BatchExecutor(db)
    batch_s = _time(lambda: bx.run_batch(reqs, infer=True), args.reps)

    per_jit_s = None
    if args.baseline:
        def per_jit():
            for t, s in reqs:
                B.run_local(t.bind(**s), db, jit=True, capacity_factor=3.0)
        per_jit_s = _time(per_jit, 1)

    bindings_per_template = {
        t.name: len(t.samples) for t, _ in reqs if t.params}
    checks = {
        # THE gate: one trace per template, no matter how many bindings or
        # how many warm passes the stream replayed
        "one_trace_per_template": srv.recompiles == n_templates,
        "cross_query_sharing": bx.shared_hits > 0,
        "no_overflow_reruns": srv.overflow_reruns == 0,
        "multi_binding_coverage": all(
            n >= 2 for n in bindings_per_template.values()),
    }
    ok = all(checks.values())

    report = {
        "sf": args.sf, "seed": args.seed, "reps": args.reps,
        "requests": len(reqs), "templates": n_templates,
        "parameterized_templates": n_param,
        "recompiles": srv.recompiles, "cache_hits": srv.cache_hits,
        "shared_hits": bx.shared_hits,
        "cold_s": round(cold_s, 4),
        "serve_s": round(serve_s, 4),
        "serve_qps": round(len(reqs) / serve_s, 2),
        "batch_s": round(batch_s, 4),
        "batch_qps": round(len(reqs) / batch_s, 2),
        "per_jit_s": None if per_jit_s is None else round(per_jit_s, 4),
        "checks": checks, "pass": bool(ok),
    }
    with open(OUT_PATH, "w") as f:
        json.dump(report, f, indent=1)

    print(f"{len(reqs)} requests over {n_templates} templates "
          f"({n_param} parameterized): cold {cold_s:.2f}s, "
          f"warm {serve_s:.2f}s ({report['serve_qps']} q/s), "
          f"batch {batch_s:.2f}s ({report['batch_qps']} q/s)")
    print(f"recompiles={srv.recompiles} cache_hits={srv.cache_hits} "
          f"shared_hits={bx.shared_hits}")
    if per_jit_s is not None:
        print(f"per-request-jit baseline {per_jit_s:.2f}s "
              f"({len(reqs) / per_jit_s:.2f} q/s)")
    for name, passed in checks.items():
        print(f"  {'ok ' if passed else 'FAIL'} {name}")
    print(f"wrote {OUT_PATH}  pass={ok}")
    if args.check and not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro import use_compile_cache
    use_compile_cache()
    main()
