"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Multi-device benches run in
subprocesses on virtual CPU devices, pinned to the CPU so this process keeps
the accelerator (see benchmarks.common.run_subprocess_bench); their rows are
host-CPU timings.  Exits non-zero if any bench failed.

  bench_tpch            Fig 10  workload performance + Table 4 counts
  bench_baseline        §6.7    engine vs CPU (NumPy) baseline
  bench_exchange        Figs 6/7 exchange microbench + Hockney fits   [8 dev]
  bench_skew            Figs 8/9/20/21 skewed exchange + JCC-H        [8 dev]
  bench_broadcast_impl  Fig 19  collective vs p2p broadcast           [8 dev]
  bench_q12_plans       Fig 22  partitioned vs non-partitioned plans  [8 dev]
  bench_projection      Figs 13/14/16 scale-out projection + QPS/$
  bench_kernels         DESIGN §6 Pallas kernels vs oracles
  bench_roofline        §Roofline table from the dry-run artifacts
"""
from __future__ import annotations

import sys

from repro import use_compile_cache

from . import (bench_baseline, bench_kernels, bench_projection,
               bench_roofline, bench_tpch)
from .common import run_subprocess_bench

SUBPROCESS = ["bench_exchange", "bench_skew", "bench_broadcast_impl",
              "bench_q12_plans"]
LOCAL = [("bench_tpch", bench_tpch), ("bench_baseline", bench_baseline),
         ("bench_projection", bench_projection),
         ("bench_kernels", bench_kernels),
         ("bench_roofline", bench_roofline)]


def main() -> None:
    use_compile_cache()
    print("name,us_per_call,derived")
    only = set(sys.argv[1:])
    failed = []
    for name, mod in LOCAL:
        if only and name not in only:
            continue
        print(f"# {name}", flush=True)
        mod.main()
    for name in SUBPROCESS:
        if only and name not in only:
            continue
        print(f"# {name} (8 virtual CPU devices, host timings)", flush=True)
        try:
            sys.stdout.write(run_subprocess_bench(name))
        except RuntimeError as e:
            print(f"# {e}", file=sys.stderr)
            failed.append(name)
        sys.stdout.flush()
    if failed:
        sys.exit(f"failed benches: {', '.join(failed)}")


if __name__ == "__main__":
    main()
