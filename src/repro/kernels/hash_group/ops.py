"""Public wrapper: dictionary sizing, row padding, rank derivation.

Capacity discipline (mirrors the hash-join bucket table): the dictionary is
sized ``next_pow2(groups_hint * capacity_factor)`` by the caller, so the fault
runner's capacity-factor escalation genuinely enlarges the dictionary on
re-execution.  Probing is bounded by a static ``rounds`` (full scan for tiny
dictionaries, a fixed window otherwise): a row that exhausts its window —
dictionary full, or an improbable murmur cluster — stays unresolved, which the
relational layer converts into the overflow flag.  Escalation lowers the load
factor, which shortens clusters, so retries converge; an undercounting
``groups_hint`` claim is NOT fixable by capacity (the group count itself
overflows) and falls to the runner's hint-drop recompilation, exactly like a
lying wire bound.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret
from repro.kernels.hash_probe.ops import _split64, next_pow2
from .kernel import hash_insert_pallas
from .ref import hash_insert_ref

# probe-window bound: tiny dictionaries are scanned in full (load factor 1.0
# still resolves); larger ones use a fixed window — at the default load
# factor <= 0.5 a 32-slot linear-probe cluster is vanishingly rare, and the
# overflow/escalation path covers the remainder
_MAX_ROUNDS = 32
# cap the (blk, cap) election tile the kernel holds in VMEM (int32 words):
# a round keeps several such tiles live, and v5e's compiler holds a kernel to
# 16 MiB of scoped VMEM (2^21 words reached 19.6 MiB at cap 8192)
_ELECT_TILE_MAX = 1 << 20


def default_rounds(cap: int) -> int:
    return min(cap, _MAX_ROUNDS)


def insert_block(cap: int) -> int:
    """Rows per grid step of the insert kernel for a ``cap``-slot
    dictionary: the (blk, cap) election tile stays within VMEM."""
    blk = 512
    while blk > 8 and blk * cap > _ELECT_TILE_MAX:
        blk //= 2
    return blk


def dict_capacity(groups_hint: int, factor: float = 2.0) -> int:
    """Dictionary slots for a claimed group bound under ``factor`` headroom."""
    return next_pow2(max(16, int(round(groups_hint * factor))))


def _merge64(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """Inverse of ``hash_probe.ops._split64`` (bit-exact)."""
    lo_u = jax.lax.bitcast_convert_type(lo, jnp.uint32).astype(jnp.int64)
    return (hi.astype(jnp.int64) << 32) | lo_u


@partial(jax.jit, static_argnames=("cap", "rounds", "use_kernel", "interpret"))
def build_group_dict(keys: jax.Array, valid: jax.Array, cap: int,
                     rounds: int | None = None, use_kernel: bool = True,
                     interpret: bool | None = None):
    """Insert-or-lookup (n,) int64 keys into a ``cap``-slot dictionary.

    Returns ``(slot, dict_keys, occupied, unresolved)``: per-row slot (int32,
    -1 = invalid or unresolved), the (cap,) int64 dictionary keys, the (cap,)
    occupancy mask, and the scalar overflow signal (some valid row could not
    be placed).  Works for ANY int64 key — negative values included — since
    slots carry exact two-plane keys, not a packed domain.
    """
    if interpret is None:
        interpret = auto_interpret()
    if rounds is None:
        rounds = default_rounds(cap)
    n = keys.shape[0]
    if not use_kernel:
        return hash_insert_ref(keys, valid, cap, rounds)
    blk = min(insert_block(cap), max(8, (n + 7) // 8 * 8))
    npad = (n + blk - 1) // blk * blk
    k = jnp.zeros((npad,), jnp.int64).at[:n].set(keys.astype(jnp.int64))
    v = jnp.zeros((npad,), jnp.int32).at[:n].set(valid.astype(jnp.int32))
    lo, hi = _split64(k)
    slot, dlo, dhi, docc = hash_insert_pallas(lo, hi, v, cap, blk=blk,
                                              rounds=rounds,
                                              interpret=interpret)
    slot = slot[:n]
    dict_keys = _merge64(dlo, dhi)
    occupied = docc == 1
    unresolved = jnp.any(valid & (slot < 0))
    return slot, dict_keys, occupied, unresolved


def dict_rank(dict_keys: jax.Array, occupied: jax.Array,
              chunk: int = 1024) -> jax.Array:
    """Ascending-key dense rank per occupied slot; ``cap`` for empty slots.

    Sort-free by construction: occupied slots hold DISTINCT keys, so
    ``rank[s] = #{t occupied : key[t] < key[s]}`` is a total order — computed
    as a chunked O(cap^2) compare over the SMALL dictionary (never the rows).
    The group-by output ordered by these ranks matches the sort path row for
    row.
    """
    cap = dict_keys.shape[0]
    parts = []
    for s0 in range(0, cap, chunk):
        ks = dict_keys[s0:s0 + chunk]
        less = (dict_keys[None, :] < ks[:, None]) & occupied[None, :]
        parts.append(jnp.sum(less, axis=1, dtype=jnp.int32))
    rank = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return jnp.where(occupied, rank, cap)
