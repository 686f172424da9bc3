"""The engine's Pallas kernels and a whole query program compile for TPU v5e.

Nothing runs: each test compiles for a described (not attached) v5e chip
with the engine's x64 switch on, at chip-sized shapes (~2^20 rows), and
checks the compiled executable holds the kernel (``tpu_custom_call``).  A
block the tiling rule refuses, an index map returning int64 or a kernel op
Mosaic cannot lower fails here instead of on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import backend as B
from repro.core import relational as rel
from repro.data import tpch
from repro.kernels.hash_group import ops as hg_ops
from repro.kernels.hash_group.kernel import hash_insert_pallas
from repro.kernels.hash_probe import ops as hp_ops
from repro.kernels.hash_probe.kernel import hash_probe64_pallas
from repro.kernels.radix_hist import ops as rh_ops
from repro.kernels.radix_hist.kernel import (counting_rank_pallas,
                                             radix_hist_pallas)
from repro.kernels.segsum import ops as ss_ops
from repro.kernels.segsum.kernel import (segment_minmax_pallas,
                                         segment_sum_pallas)
from repro.queries import QUERIES

N = 1 << 20
CAP = hg_ops.dict_capacity(rel.HASH_AGG_GROUPS_MAX, 4.0)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


I32, F32 = jnp.int32, jnp.float32
KERNELS = {
    "segment_sum_pallas": (lambda g, v: segment_sum_pallas(g, v, 256),
                           [((N,), I32), ((N, 128), F32)]),
    "segment_minmax_pallas": (
        lambda g, v: segment_minmax_pallas(g, v, 256, is_min=True),
        [((N,), I32), ((N,), F32)]),
    # the direct group-by's largest domain and the hash group-by's largest
    # dictionary, through the wrapper that picks the group and row blocks
    "segment_reduce_sum_direct_max": (
        lambda g, v: ss_ops.segment_reduce(
            g, v, 1 << rel.DIRECT_AGG_BITS_MAX, op="sum", interpret=False),
        [((N,), I32), ((N, 3), F32)]),
    "segment_reduce_max_direct_max": (
        lambda g, v: ss_ops.segment_reduce(
            g, v, 1 << rel.DIRECT_AGG_BITS_MAX, op="max", interpret=False),
        [((N,), I32), ((N,), F32)]),
    "segment_reduce_count_hash_cap": (
        lambda g: ss_ops.segment_reduce(g, None, CAP, op="count",
                                        interpret=False),
        [((N,), I32)]),
    "radix_hist_pallas": (lambda k: radix_hist_pallas(k, 4),
                          [((N,), I32)]),
    "counting_rank_pallas": (lambda k: counting_rank_pallas(k, 5, 128),
                             [((N,), I32)]),
    # the relational hash join's table for an 8192-row build side
    "hash_probe64_pallas": (hash_probe64_pallas,
                            [((N,), I32)] * 2 + [((4096, 16), I32)] * 3),
    # the largest dictionary of a hash group-by once escalated: its groups
    # bound (4096) times twice the default headroom
    "hash_insert_pallas": (
        lambda lo, hi, valid: hash_insert_pallas(
            lo, hi, valid, CAP, blk=hg_ops.insert_block(CAP),
            rounds=hg_ops.default_rounds(CAP)),
        [((N,), I32)] * 3),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    assert jax.config.jax_enable_x64
    text = _compile_text(fn, *shapes, sharding=one_chip)
    assert "tpu_custom_call" in text, name


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the kernel wrappers to compile (``auto_interpret`` sees the
    CPU here); traces made meanwhile are dropped afterwards."""
    for ops in (ss_ops, rh_ops, hp_ops, hg_ops):
        monkeypatch.setattr(ops, "auto_interpret", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_q1_program_compiles_for_v5e(one_chip, compiled_kernels):
    db = tpch.generate(0.175, seed=0)            # lineitem ~2^20 rows
    tables = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        B._np_db_to_tables(db))
    assert tables["lineitem"].capacity >= N - N // 16

    def q1(tables):
        ctx = B.LocalContext(db, tables, use_kernel=True)
        return rel.ensure_compact(QUERIES[1](ctx)), ctx.overflow

    text = jax.jit(q1).lower(tables).compile().as_text()
    # Q1's group-by runs on the direct path: the count kernel is compiled in
    assert "tpu_custom_call" in text


def test_direct_join_compiles_for_v5e(one_chip):
    """The direct-address join index at chip size (a 2^20-row build over a
    domain four times as wide, probed by 2^22 rows) compiles for v5e to one
    scatter and one gather: no sort, no loop."""
    from repro.core.table import Table
    from repro.distributed.hlo_analysis import op_histogram
    span = 4 * N

    def join(bk, pk):
        build = Table({"k": bk}, jnp.asarray(N, jnp.int32))
        idx = rel.build_index(build, bk, method="direct", key_range=(1, span))
        matched, rows = rel.probe_index(idx, pk, jnp.ones(pk.shape, bool))
        return matched, rows, idx.overflow

    text = _compile_text(join, ((N,), jnp.int64), ((4 * N,), jnp.int64),
                         sharding=one_chip)
    assert op_histogram(text, ops=("sort", "while", "gather", "scatter")) \
        == {"sort": 0, "while": 0, "gather": 1, "scatter": 1}


def test_q6_spmd_program_compiles_for_v5e_2x2(topo, compiled_kernels):
    """The four-chip path: an SPMD program (``backend.spmd_program``, the
    server's and run_distributed's) over a 2x2 mesh, with
    ~2^20 lineitem rows per chip, holds its all-reduces and its kernel."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    db = tpch.generate(0.7, seed=0)
    sharded, caps = B.partition_database(db, 4)
    assert caps["lineitem"] >= N - N // 16
    spec = NamedSharding(mesh, P("data"))
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=spec),
        sharded)
    fn = B.spmd_program(lambda ctx, _: QUERIES[6](ctx), db, mesh,
                        use_kernel=True)
    text = fn.lower(shapes, {}).compile().as_text()
    assert "all-reduce" in text and "tpu_custom_call" in text
