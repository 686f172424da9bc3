"""Paper Figs. 8/9 (exchange under skew), 20/21 (JCC-H memory + per-query).

Shuffle with a skew gradient f (the paper's synthetic placement: device i
holds x + i*f*x rows) — broadcast unaffected, shuffle degraded; plus JCC-H
partition imbalance and the per-query comparison of §7.2.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import backend as B
from repro.core.exchange import broadcast_table, shuffle
from repro.core.table import Table
from repro.data import jcch, tpch
from repro.queries import QUERIES
from repro.core.compat import make_mesh, shard_map

from .common import emit, time_fn

N = 8
BASE_ROWS = 1 << 14


def _skewed_counts(f: float) -> np.ndarray:
    """Device i holds x*(1+i*f) rows, total fixed at N*BASE_ROWS."""
    w = 1 + np.arange(N) * f
    return np.maximum(8, (BASE_ROWS * N * w / w.sum()).astype(np.int64))


def main():
    mesh = make_mesh((N,), ("data",))
    cap = BASE_ROWS * 4
    for f in (0.0, 0.5, 1.0, 2.0):
        counts = _skewed_counts(f)

        @jax.jit
        def do_shuffle(cnts):
            def body(c):
                rows = cap
                t = Table({"k": jnp.arange(rows, dtype=jnp.int64),
                           "v": jnp.ones((rows,), jnp.float64)},
                          c[0].astype(jnp.int32))
                out, ov, _, _ = shuffle(t, t["k"], "data", N,
                                        cap_per_dest=cap)
                return out.count.reshape(1)
            return shard_map(body, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"))(cnts)

        @jax.jit
        def do_broadcast(cnts):
            def body(c):
                t = Table({"k": jnp.arange(cap, dtype=jnp.int64),
                           "v": jnp.ones((cap,), jnp.float64)},
                          c[0].astype(jnp.int32))
                out, _, _ = broadcast_table(t, "data", N)
                return out.count.reshape(1)
            return shard_map(body, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"))(cnts)

        arg = jnp.asarray(counts)
        t_sh = time_fn(do_shuffle, arg, iters=3)
        t_bc = time_fn(do_broadcast, arg, iters=3)
        emit(f"skew_shuffle_f{f}", t_sh * 1e6,
             f"imbalance={counts.max() / counts.mean():.2f}")
        emit(f"skew_broadcast_f{f}", t_bc * 1e6,
             f"imbalance={counts.max() / counts.mean():.2f}")

    # JCC-H vs TPC-H: partition imbalance (the paper's Fig 20 proxy: peak
    # memory tracks partition size under our static-capacity tables)
    sf = 0.005
    uni = tpch.generate(sf, seed=11)
    skw = jcch.generate(sf, seed=11, skew=0.3)
    for name, db in (("tpch", uni), ("jcch", skw)):
        # partition by the skewed FK (the paper's Fig 20 memory imbalance)
        parts, caps = B.partition_database(
            db, N, partition_keys={"lineitem": "l_partkey"})
        c = parts["lineitem"]["__count"]
        emit(f"{name}_lineitem_imbalance", float(c.max()) / float(c.mean()) * 100,
             f"max={int(c.max())};mean={c.mean():.0f};cap={caps['lineitem']}")
    # per-query (Fig 21): Q4 / Q13 under uniform vs skewed data
    for qid in (4, 13):
        for name, db in (("tpch", uni), ("jcch", skw)):
            def run():
                out, _, ov = B.run_distributed(QUERIES[qid], db, mesh,
                                               capacity_factor=4.0)
                assert not ov
                return out
            t = time_fn(lambda: run(), warmup=1, iters=2)
            emit(f"q{qid}_{name}_dist8", t * 1e6, "")


if __name__ == "__main__":
    from repro import use_compile_cache
    use_compile_cache()
    main()
