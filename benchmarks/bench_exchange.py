"""Paper Figs. 6/7 (exchange microbenchmarks + model validation) and the
Hockney fits used by the projections.  Runs under 8 virtual host devices
(spawned by run.py); wall times are CPU-host times, so the *trend* (latency
floor, bandwidth saturation, model fit quality) is the deliverable, and the
fitted constants parameterize B_n(m)/B_g(m) exactly as the paper's §3.6.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import perfmodel as pm
from repro.core.exchange import broadcast_table, shuffle
from repro.core.table import Table
from repro.core.compat import make_mesh, shard_map

from .common import emit, time_fn

N = 8
SIZES_LOG2 = range(10, 19)   # rows per device: 1k .. 256k (x8 bytes/row)


def _mktable(rows: int) -> Table:
    cols = {"k": jnp.arange(rows, dtype=jnp.int64),
            "v": jnp.ones((rows,), jnp.float64)}
    return Table(cols, jnp.asarray(rows, jnp.int32))


def main():
    mesh = make_mesh((N,), ("data",))
    meas = {"shuffle": [], "broadcast": []}
    for lg in SIZES_LOG2:
        rows = 1 << lg
        bytes_per_dev = rows * 16          # two 8-byte columns

        @jax.jit
        def do_shuffle(key0):
            def body(_):
                t = _mktable(rows)
                out, ov, _, _ = shuffle(t, t["k"] + key0, "data", N,
                                        cap_per_dest=rows // N * 4)
                return out.count.reshape(1)
            return shard_map(body, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"))(
                jnp.zeros((N,), jnp.int64))

        @jax.jit
        def do_broadcast(key0):
            def body(_):
                t = _mktable(rows)
                out, _, _ = broadcast_table(t, "data", N)
                return out.count.reshape(1)
            return shard_map(body, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"))(
                jnp.zeros((N,), jnp.int64))

        t_sh = time_fn(do_shuffle, jnp.asarray(0, jnp.int64), iters=5)
        t_bc = time_fn(do_broadcast, jnp.asarray(0, jnp.int64), iters=5)
        total = bytes_per_dev * N
        meas["shuffle"].append((total / (N * N), t_sh))   # p2p msg size
        meas["broadcast"].append((bytes_per_dev, t_bc))   # ring payload
        emit(f"shuffle_{1 << lg}rows", t_sh * 1e6,
             f"thpt_GBps={total / t_sh / 1e9:.3f};msg_bytes={total // (N * N)}")
        emit(f"broadcast_{1 << lg}rows", t_bc * 1e6,
             f"thpt_GBps={total / t_bc / 1e9:.3f};msg_bytes={bytes_per_dev}")

    # Hockney fits (paper fits V=2 microbenchmarks; we fit the sweep)
    for kind in ("shuffle", "broadcast"):
        ms = np.array([m for m, _ in meas[kind]], dtype=np.float64)
        ts = np.array([t for _, t in meas[kind]], dtype=np.float64)
        fit = pm.fit_hockney(ms, ts)
        emit(f"hockney_{kind}", fit.latency * 1e6,
             f"inv_bw_s_per_byte={fit.inv_bw:.3e};"
             f"bw_at_1MB_GBps={fit.bandwidth(1e6) / 1e9:.3f}")
        # model validation: predicted vs measured at the largest size
        m_big, t_big = meas[kind][-1]
        pred = fit.time(m_big)
        emit(f"model_check_{kind}", pred * 1e6,
             f"measured_us={t_big * 1e6:.1f};"
             f"rel_err={abs(pred - t_big) / t_big:.3f}")


if __name__ == "__main__":
    from repro import use_compile_cache
    use_compile_cache()
    main()
