"""Public wrapper: padding, alignment, interpret switch, CPU fallback.

Dead-slot convention
--------------------
Rows the caller wants excluded (table padding, invalid rows, out-of-domain
keys) are routed to the **dead slot, which is always index ``groups``** — the
first id beyond the real group range.  The padded group width ``gpad`` is
``groups + 1`` rounded up to the 128-lane tile (above 1024, to the kernels'
1024-group block), so the dead slot exists for every ``groups`` and is never
lane-boundary dependent.  (The previous scheme
parked padding rows at ``gpad - 1``; at exact lane boundaries —
``groups == gpad - 1``, e.g. groups = 127/255 — a caller-side sentinel id
``groups`` and the wrapper's dead row could alias real/dead slots depending
on how ``gpad`` was derived.  Pinning the dead slot to ``groups`` removes the
boundary case entirely; see tests/test_aggregate_paths.py.)

Out-of-range gids (negative or > groups) are rerouted to the dead slot before
the kernel runs, so garbage ids can never scribble into a real group.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import segment_minmax_pallas, segment_sum_pallas
from repro.kernels import auto_interpret
from .ref import segment_reduce_ref, segment_sum_ref

_LANES = 128
# VMEM: the kernels sweep the rows once per block of at most _GROUP_BLOCK
# groups, with (blk, gblk) tiles of at most _TILE_MAX elements (2 MiB of
# float32), so a 2^13-group domain stays inside v5e's 16 MiB scoped VMEM
_GROUP_BLOCK = 1024
_TILE_MAX = 1 << 19
# one-hot f32 count matmuls are exact while the row count fits the mantissa
_F32_EXACT_ROWS = 1 << 24


def _pad_to(x: int, m: int) -> int:
    return max(m, (x + m - 1) // m * m)


def _route_dead(gids: jax.Array, groups: int) -> jax.Array:
    """Clamp out-of-range ids to the dead slot (= ``groups``)."""
    g = gids.astype(jnp.int32)
    return jnp.where((g < 0) | (g > groups), groups, g)


def _pad_rows(gids: jax.Array, groups: int, blk: int) -> tuple[jax.Array, int, int]:
    """(padded gids, padded length, effective blk); padding rows -> dead slot."""
    n = gids.shape[0]
    blk = min(blk, _pad_to(n, 8))
    npad = _pad_to(n, blk)
    g2 = jnp.full((npad,), groups, jnp.int32).at[:n].set(
        _route_dead(gids, groups))
    return g2, npad, blk


def _group_blocks(groups: int, blk: int) -> tuple[int, int, int]:
    """(padded group width, group block, row block) for ``groups`` real
    groups plus the dead slot."""
    gpad = _pad_to(groups + 1, _LANES)
    if gpad > _GROUP_BLOCK:
        gpad = _pad_to(gpad, _GROUP_BLOCK)
    gblk = min(gpad, _GROUP_BLOCK)
    return gpad, gblk, min(blk, _TILE_MAX // gblk // 8 * 8)


def _sum_kernel(gids: jax.Array, values: jax.Array, groups: int, blk: int,
                interpret: bool) -> jax.Array:
    """values (n, C) float32/float64 -> (groups, C), via the MXU kernel."""
    n, c = values.shape
    gpad, gblk, blk = _group_blocks(groups, blk)
    cpad = _pad_to(c, _LANES)
    g2, npad, blk = _pad_rows(gids, groups, blk)
    v2 = jnp.zeros((npad, cpad), values.dtype).at[:n, :c].set(values)
    out = segment_sum_pallas(g2, v2, gpad, blk=blk, gblk=gblk,
                             interpret=interpret)
    return out[:groups, :c]


def _minmax_kernel(gids: jax.Array, values: jax.Array, groups: int, op: str,
                   blk: int, interpret: bool) -> jax.Array:
    """values (n,) float -> (groups,) min/max via the masked-reduce kernel."""
    n = values.shape[0]
    gpad, gblk, blk = _group_blocks(groups, blk)
    ident = jnp.asarray(jnp.inf if op == "min" else -jnp.inf, values.dtype)
    g2, npad, blk = _pad_rows(gids, groups, blk)
    v2 = jnp.full((npad,), ident, values.dtype).at[:n].set(values)
    out = segment_minmax_pallas(g2, v2, gpad, is_min=(op == "min"),
                                blk=blk, gblk=gblk, interpret=interpret)
    return out[:groups]


def _kernel_dtype_ok(dt, interpret: bool) -> bool:
    """float32 everywhere; float64 only under interpret (no f64 MXU)."""
    return dt == jnp.float32 or (dt == jnp.float64 and interpret)


@partial(jax.jit, static_argnames=("groups", "op", "blk", "interpret",
                                   "use_kernel"))
def segment_reduce(gids: jax.Array, values: jax.Array | None, groups: int,
                   op: str = "sum", blk: int = 1024,
                   interpret: bool | None = None,
                   use_kernel: bool = True) -> jax.Array:
    """Sortless grouped reduction: sum / count / min / max, dtype-preserving.

    The TPU fast path is the one-hot MXU matmul (sum/count) or the one-hot
    masked lane reduce (min/max); dtypes the hardware kernels cannot hold
    exactly (integers, float64 outside interpret mode) fall back to jnp
    scatter-reduce — still sortless, still dead-slot routed.  ``op="count"``
    ignores ``values`` and returns int64 row counts per group.
    ``interpret=None`` auto-selects: compiled on TPU, interpret elsewhere.
    """
    if interpret is None:
        interpret = auto_interpret()
    if op == "count":
        n = gids.shape[0]
        if use_kernel and n < _F32_EXACT_ROWS:
            out = _sum_kernel(gids, jnp.ones((n, 1), jnp.float32), groups,
                              blk, interpret)[:, 0]
            return jnp.round(out).astype(jnp.int64)
        return segment_reduce_ref(_route_dead(gids, groups),
                                  jnp.ones((n,), jnp.int64), groups, "sum")
    if op not in ("sum", "min", "max"):
        raise ValueError(f"unknown segment reduce op {op!r}")
    squeeze = values.ndim == 1
    v = values[:, None] if squeeze else values
    kernel_ok = use_kernel and jnp.issubdtype(v.dtype, jnp.floating) and \
        _kernel_dtype_ok(v.dtype, interpret)
    if op == "sum":
        if kernel_ok:
            out = _sum_kernel(gids, v, groups, blk, interpret)
        else:
            out = segment_reduce_ref(_route_dead(gids, groups), v, groups,
                                     "sum")
    else:
        if kernel_ok:
            cols = [_minmax_kernel(gids, v[:, i], groups, op, blk, interpret)
                    for i in range(v.shape[1])]
            out = jnp.stack(cols, axis=1)
        else:
            out = segment_reduce_ref(_route_dead(gids, groups), v, groups, op)
    return out[:, 0] if squeeze else out


@partial(jax.jit, static_argnames=("groups", "blk", "interpret", "use_kernel"))
def segment_sum(gids: jax.Array, values: jax.Array, groups: int,
                blk: int = 1024, interpret: bool | None = None,
                use_kernel: bool = True) -> jax.Array:
    """Grouped float32 sum with the MXU one-hot kernel (legacy entry point).

    values may be (n,) or (n, C); output is float32.  Padding rows and
    out-of-range gids route to the dead slot (see module docstring).  With
    use_kernel=False the jnp oracle runs (the production config flips this on
    non-TPU backends).  ``segment_reduce`` is the dtype-preserving superset.
    """
    if interpret is None:
        interpret = auto_interpret()
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    if not use_kernel:
        out = segment_sum_ref(_route_dead(gids, groups), values, groups)
        return out[:, 0] if squeeze else out
    out = _sum_kernel(gids, values.astype(jnp.float32), groups, blk, interpret)
    return out[:, 0] if squeeze else out
