"""Kernel microbenches: Pallas vs jnp oracle per hot spot.

The derived column names the engine kernels' Pallas mode: compiled on TPU,
interpret elsewhere (flash attention always interprets).  On CPU the
interpreter is orders of magnitude slower than compiled jnp — the derived
column carries the structural facts that matter for the TPU target
(tile shapes, VMEM footprint), not the wall time ratio.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.kernels import auto_interpret
from repro.kernels.flash_attention import ops as fa
from repro.kernels.hash_probe import ops as hp
from repro.kernels.radix_hist import ops as rh
from repro.kernels.segsum import ops as ss

from .common import emit, time_fn

rng = np.random.default_rng(0)


def main():
    mode = "interpret" if auto_interpret() else "compiled"
    n, g, c = 8192, 256, 8
    gids = jnp.asarray(rng.integers(0, g, n).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(n, c)).astype(np.float32))
    t = time_fn(lambda: ss.segment_sum(gids, vals, g, use_kernel=False),
                iters=5)
    emit("segsum_ref_8k_256g", t * 1e6, "jnp oracle")
    t = time_fn(lambda: ss.segment_sum(gids, vals, g, blk=1024), iters=3)
    emit("segsum_pallas_8k_256g", t * 1e6,
         f"{mode};vmem_bytes={1024 * (384 + 128) * 4 + 384 * 128 * 4}")

    keys = jnp.asarray(rng.integers(0, 1 << 31, 8192).astype(np.int32))
    t = time_fn(lambda: rh.radix_hist(keys, 64, use_kernel=False), iters=5)
    emit("radix_hist_ref_8k_64p", t * 1e6, "jnp oracle")
    t = time_fn(lambda: rh.radix_hist(keys, 64, blk=2048), iters=3)
    emit("radix_hist_pallas_8k_64p", t * 1e6, mode)

    bkeys = jnp.asarray(rng.choice(1 << 30, 1024, replace=False)
                        .astype(np.int32))
    bvals = jnp.arange(1024, dtype=jnp.int32)
    pkeys = jnp.asarray(rng.integers(0, 1 << 30, 8192).astype(np.int32))
    t = time_fn(lambda: hp.hash_join_probe(pkeys, bkeys, bvals,
                                           use_kernel=False)[0], iters=5)
    emit("hash_probe_ref_8k", t * 1e6, "searchsorted oracle")
    t = time_fn(lambda: hp.hash_join_probe(pkeys, bkeys, bvals, cap=16)[0],
                iters=3)
    emit("hash_probe_pallas_8k", t * 1e6, f"{mode};bucket_cap=16")

    q = jnp.asarray(rng.normal(size=(1, 4, 256, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)).astype(np.float32))
    t = time_fn(lambda: fa.flash_attention(q, k, k, use_kernel=False),
                iters=5)
    emit("flashattn_ref_256", t * 1e6, "jnp oracle")
    t = time_fn(lambda: fa.flash_attention(q, k, k, q_blk=128, kv_blk=128),
                iters=2)
    emit("flashattn_pallas_256", t * 1e6, "interpret;q_blk=128;kv_blk=128")


if __name__ == "__main__":
    from repro import use_compile_cache
    use_compile_cache()
    main()
