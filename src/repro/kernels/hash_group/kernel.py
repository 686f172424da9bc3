"""Hash-compaction dictionary build for sortless group-by on unknown domains.

The direct-addressing aggregation (``kernels/segsum``) needs the packed group
key to BE the dense group id, which requires *provable* ``key_bits``.  Q13-style
keys (orders-per-customer) are data-dependent: the domain is small but cannot
be proved at plan time.  GPU engines answer this with a hash aggregation table
built by atomics; the TPU adaptation here is a **write-once open-addressing
dictionary built in VMEM across a sequential row-block grid** — the same
trick ``radix_hist.counting_rank`` uses for its running totals:

  * the dictionary is three ``(1, cap)`` VMEM scratch rows — two int32 key
    planes holding the full 64-bit key (the ``hash_probe`` two-plane scheme,
    probed with the SAME ``bucket_of`` mix so both kernels hash identically)
    plus an occupancy plane — carried across grid steps;
  * each block's rows probe in lockstep rounds (linear probing from
    ``bucket_of(key)``): a round reads the candidate slot, resolves rows
    whose key already sits there, and elects ONE writer per empty slot by a
    one-hot minimum over row indices — no atomics, no scatter, and a slot
    transitions empty -> occupied exactly once (write-once), so a resolved
    row's slot can never be stolen by a later key;
  * rows that exhaust ``rounds`` probes stay unresolved (``slot = -1``) — the
    caller raises the overflow flag and the fault runner re-executes with a
    larger dictionary (capacity-factor escalation), never silently merging or
    dropping groups.

The kernel returns hash-ordered slots; the wrapper (``ops.dict_rank``) turns
occupied slots into ascending-key dense ids with an O(cap^2) chunked compare
(cap is the SMALL dictionary, not the row count) so the aggregation output is
ordered identically to the sort path, byte for byte.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import I32_ZERO as _ZERO
from repro.kernels.hash_probe.kernel import bucket_of


def _pick(onehot: jax.Array, plane: jax.Array, axis: int) -> jax.Array:
    """The one element of ``plane`` that ``onehot`` selects along ``axis``
    (a select + exact int32 sum — Mosaic has no gather from a VMEM table)."""
    return jnp.sum(jnp.where(onehot, plane, _ZERO), axis=axis, keepdims=True,
                   dtype=jnp.int32)


def _insert_kernel(plo_ref, phi_ref, pv_ref, slot_ref, dlo_ref, dhi_ref,
                   docc_ref, tlo, thi, tocc, *, blk: int, cap: int,
                   rounds: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        tlo[...] = jnp.zeros_like(tlo)
        thi[...] = jnp.zeros_like(thi)
        tocc[...] = jnp.zeros_like(tocc)

    lo = plo_ref[...]                                         # (blk, 1)
    hi = phi_ref[...]
    b = bucket_of(lo, hi, cap)
    rows = jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0)   # (blk, 1)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (blk, cap), 1)
    big = np.int32(blk)
    one = np.int32(1)

    def lookup(s_hot, unres, out, s):
        # resolve rows whose key already sits in their candidate slot
        hit = (unres == one) & (_pick(s_hot, tocc[...], 1) == one) & \
            (_pick(s_hot, tlo[...], 1) == lo) & \
            (_pick(s_hot, thi[...], 1) == hi)
        return jnp.where(hit, _ZERO, unres), jnp.where(hit, s, out)

    def body(carry):
        r, unres, out = carry                                 # (blk, 1) i32
        s = jax.lax.rem(b + r, np.int32(cap))                 # linear probe
        s_hot = s == iota_c                                   # (blk, cap)
        unres, out = lookup(s_hot, unres, out, s)
        # elect ONE writer per still-empty slot: min row index attempting
        att = (unres == one) & (_pick(s_hot, tocc[...], 1) == _ZERO)
        m = att & s_hot                                       # (blk, cap)
        win = jnp.min(jnp.where(m, rows, big), axis=0,
                      keepdims=True)                          # (1, cap)
        has = win < big
        won = m & (rows == win)                   # the winner's one-hot row
        tlo[...] = jnp.where(has, _pick(won, lo, 0), tlo[...])
        thi[...] = jnp.where(has, _pick(won, hi, 0), thi[...])
        tocc[...] = jnp.where(has, one, tocc[...])
        # losers see the winner's key on the re-lookup and probe on
        return (r + one,) + lookup(s_hot, unres, out, s)

    unres0 = (pv_ref[...] != _ZERO).astype(jnp.int32)
    out0 = jnp.full((blk, 1), np.int32(-1))
    # an int32 round counter: fori_loop would carry an int64 one under x64
    _, _, out = jax.lax.while_loop(lambda c: c[0] < np.int32(rounds), body,
                                   (_ZERO, unres0, out0))
    slot_ref[...] = out
    # the dictionary outputs are pinned to block 0: the last grid step's write
    # is the final table (cheap — cap is small)
    dlo_ref[...] = tlo[...]
    dhi_ref[...] = thi[...]
    docc_ref[...] = tocc[...]


def hash_insert_pallas(plo: jax.Array, phi: jax.Array, pvalid: jax.Array,
                       cap: int, blk: int = 512, rounds: int = 16,
                       interpret: bool = False):
    """Insert-or-lookup of (n,) int32 key planes into a (cap,) dictionary.

    Returns ``(slot, dict_lo, dict_hi, occupied)``: per-row dictionary slot
    (int32, ``-1`` = invalid or unresolved after ``rounds`` probes) plus the
    final key planes and int32 occupancy of the dictionary.

    VMEM working set: 3 ``(1, cap)`` scratch rows resident across the
    sequential grid + the ``(blk, cap)`` election tile per round — callers
    bound ``blk * cap`` (``ops.build_group_dict`` does).
    """
    n = plo.shape[0]
    assert n % blk == 0, (n, blk)
    grid = (n // blk,)
    row = pl.BlockSpec((blk, 1), lambda i: (i, _ZERO))
    table = pl.BlockSpec((1, cap), lambda i: (_ZERO, _ZERO))  # resident
    slot, dlo, dhi, docc = pl.pallas_call(
        functools.partial(_insert_kernel, blk=blk, cap=cap, rounds=rounds),
        grid=grid,
        in_specs=[row] * 3,
        out_specs=[row] + [table] * 3,
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.int32)] +
        [jax.ShapeDtypeStruct((1, cap), jnp.int32)] * 3,
        scratch_shapes=[pltpu.VMEM((1, cap), jnp.int32)] * 3,
        interpret=interpret,
    )(plo.reshape(n, 1), phi.reshape(n, 1), pvalid.reshape(n, 1))
    return slot[:, 0], dlo[0], dhi[0], docc[0]
