"""Paper Fig. 10 (workload performance) + Table 4 (exchange counts).

Single-device engine timings per query (this container's CPU stands in for
one device; the distributed variant runs in bench_exchange subprocesses) and
the per-plan exchange statistics that reproduce Table 4.
"""
from __future__ import annotations

from repro.core import backend as B
from repro.data import tpch
from repro.queries import PAPER_TABLE4, QUERIES

from .common import emit, time_fn

SF = 0.01


def main():
    db = tpch.generate(SF, seed=11)
    total = 0.0
    for qid in sorted(QUERIES):
        import jax

        fn = QUERIES[qid]
        holder = {}

        def run():
            out, stats = B.run_local(fn, db)
            holder["stats"] = stats
            return out

        t = time_fn(lambda: run(), warmup=1, iters=3)
        total += t
        s = holder["stats"]
        pc = PAPER_TABLE4.get(qid, (None, None))
        emit(f"tpch_q{qid}", t * 1e6,
             f"sf={SF};shuffles={s.shuffles};broadcasts={s.broadcasts};"
             f"paper_shuffles={pc[0]};paper_broadcasts={pc[1]}")
    emit("tpch_total_22q", total * 1e6, f"sf={SF};single_device")


if __name__ == "__main__":
    from repro import use_compile_cache
    use_compile_cache()
    main()
