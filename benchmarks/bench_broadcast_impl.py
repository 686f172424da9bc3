"""Paper Fig. 19 / §7.1: collective broadcast vs p2p-emulated broadcast.

The p2p ring forwards the full shard N-1 times (duplicated inter-node
traffic); the collective all_gather pipelines it.  We report wall time AND
the structural byte counts the perf model uses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.exchange import broadcast_table, broadcast_table_p2p
from repro.core.table import Table
from repro.core.compat import make_mesh, shard_map

from .common import emit, time_fn

N = 8


def main():
    mesh = make_mesh((N,), ("data",))
    for lg in (12, 15, 18):
        rows = 1 << lg
        stats_holder = {}

        def make(p2p: bool):
            @jax.jit
            def run(x):
                def body(_):
                    t = Table({"k": jnp.arange(rows, dtype=jnp.int64),
                               "v": jnp.ones((rows,), jnp.float64)},
                              jnp.asarray(rows, jnp.int32))
                    if p2p:
                        out, st = broadcast_table_p2p(t, "data", N)
                    else:
                        out, _, st = broadcast_table(t, "data", N)
                    stats_holder[p2p] = st
                    return out.count.reshape(1)
                return shard_map(body, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data"))(x)
            return run

        x = jnp.zeros((N,), jnp.int32)
        t_coll = time_fn(make(False), x, iters=5)
        t_p2p = time_fn(make(True), x, iters=5)
        st_c, st_p = stats_holder[False], stats_holder[True]
        emit(f"broadcast_collective_{rows}rows", t_coll * 1e6,
             f"collectives={st_c.collectives};bytes={st_c.total_bytes}")
        emit(f"broadcast_p2p_{rows}rows", t_p2p * 1e6,
             f"collectives={st_p.collectives};bytes={st_p.total_bytes};"
             f"slowdown={t_p2p / t_coll:.2f}x")


if __name__ == "__main__":
    from repro import use_compile_cache
    use_compile_cache()
    main()
