"""Paper §6.7 analogue: tensor engine vs the single-node CPU baseline.

The paper compares against DuckDB; our NumPy reference executor is the
CPU baseline (independent implementation, exact-size arrays).  Both run the
same 22 logical plans.
"""
from __future__ import annotations

from repro.core import backend as B
from repro.data import tpch
from repro.queries import QUERIES

from .common import emit, time_fn

SF = 0.01


def main():
    db = tpch.generate(SF, seed=11)
    t_engine = 0.0
    t_base = 0.0
    for qid in sorted(QUERIES):
        fn = QUERIES[qid]
        te = time_fn(lambda: B.run_local(fn, db)[0], warmup=1, iters=3)
        tb = time_fn(lambda: B.run_reference(fn, db)[0], warmup=0, iters=3)
        t_engine += te
        t_base += tb
    emit("baseline_numpy_22q", t_base * 1e6, f"sf={SF}")
    emit("engine_jax_22q", t_engine * 1e6,
         f"sf={SF};note=both run on the same CPU here - the engine pays "
         f"static-shape padding+sorting for TPU-native execution; the "
         f"paper's GPU-vs-CPU-DB gap is projected in bench_projection")


if __name__ == "__main__":
    from repro import use_compile_cache
    use_compile_cache()
    main()
