"""End-to-end distributed analytics driver (the paper's Figure 1b workflow).

Runs the full 22-query TPC-H workload SPMD over every device JAX sees (the
chips of a TPU host, or 8 virtual CPU devices without one) with the
fault-tolerant runner: host-partitioned load (§4.3), capacity-bounded
collective exchanges, re-execution on overflow, per-query exchange stats.

    PYTHONPATH=src python examples/analytics_distributed.py [--sf 0.01]
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax

from repro import use_compile_cache
from repro.data import tpch
from repro.distributed.fault import QueryRunner
from repro.queries import QUERIES
from repro.core.compat import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--queries", type=str, default="")
    args = ap.parse_args()

    use_compile_cache()
    n = len(jax.devices())
    mesh = make_mesh((n,), ("data",))
    print(f"devices={n}  scale factor={args.sf}")
    db = tpch.generate(args.sf, seed=7)
    runner = QueryRunner(db, mesh, capacity_factor=2.5)

    qids = ([int(q) for q in args.queries.split(",") if q]
            or sorted(QUERIES))
    total = 0.0
    for qid in qids:
        res = runner.run(QUERIES[qid])
        total += res.wall_s
        nrows = len(next(iter(res.result.values()))) if res.result else 0
        print(f"Q{qid:2d}  {res.wall_s * 1e3:9.1f} ms  rows={nrows:5d}  "
              f"shuffles={res.stats.shuffles} "
              f"broadcasts={res.stats.broadcasts} "
              f"attempts={res.attempts}")
    print(f"\nall {len(qids)} queries: {total:.2f} s "
          f"(includes trace+compile on first run of each)")


if __name__ == "__main__":
    main()
