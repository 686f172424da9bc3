"""Data-exchange operators on JAX collectives — the paper's core contribution.

GPU/NCCL -> TPU/XLA mapping (DESIGN.md §2):

  shuffle    NCCL N^2 ncclSend/Recv (variable sizes)  ->  capacity-bounded
             ``jax.lax.all_to_all`` with per-destination fixed-size row buffers
             and validity counts (the MoE-dispatch idiom).
  broadcast  ncclBroadcast one-to-all ring             ->  ``jax.lax.all_gather``
             (XLA lowers to the ICI ring — exactly the paper's Eq. 1 model).
             A deliberately-naive p2p ring variant (``broadcast_table_p2p``)
             reproduces §7.1 / Figure 19.
  allreduce  ncclAllReduce                             ->  ``jax.lax.psum`` etc.

Wire format (packed exchanges)
------------------------------
Columns are exchanged either one at a time (paper-faithful, §2.3 "we exchange
one column at a time") or packed into a single int32 buffer so the whole
table moves in ONE collective.  The packed layout is a planner-statistics-
driven **wire format** (:mod:`repro.core.wire`):

  * **Lane layout** — with per-column ``(lo, hi)`` bounds (the same min/max
    statistics that feed ``key_bits``), integer columns ship biased at their
    inferred width: 8/16-bit lanes share int32 words via shift/or, a 64-bit
    column whose span fits 32 bits ships as one biased word, a provably
    constant column is not shipped at all, and bool is always an 8-bit lane.
    float64 stays split across two words — mantissas cannot be range-
    compressed — and anything unbounded ships verbatim.  ``REPRO_WIRE=wide``
    forces the legacy full-width layout (the differential leg); without
    planner bounds the format is wide by construction.
  * **Header row** — the paper's pre-exchange size-metadata round is FUSED
    into the payload: row 0 of each per-destination block (word 0) carries
    the sender's row count, so a packed ``shuffle``/``broadcast_table`` is
    ONE collective, not a counts round plus a payload round.  The per-column
    mode keeps the separate metadata round (it is the §2.3 baseline).
  * **Overflow contract** — a narrowed column is range-checked per valid row
    at pack time; a value outside its claimed bounds sets the returned
    overflow flag (ORed into ``ctx.overflow`` -> the fault runner re-executes,
    dropping inference and hence the narrow format).  Lying bounds can
    therefore cost a retry but can never silently truncate a value.
  * **Integrity word** — packed exchanges fold an integrity checksum of each
    per-sender payload block into the same fused header row
    (:func:`repro.core.wire.header_mode`); receivers verify every block and
    raise the ``corrupt`` flag on mismatch (ORed into ``ctx.corrupt`` -> the
    fault runner re-executes on the wide format).  The ``tamper`` hook lets
    the chaos harness flip received payload bits inside the traced program.

``ExchangeStats`` reports both actual wire bytes (packed words incl. the
header row) and logical dtype-true bytes, so the compression ratio is visible
per exchange and the §3.6 Hockney model consumes what actually moves
(:func:`repro.core.perfmodel.exchange_time_from_stats`).

Deferred compaction: exchange OUTPUTS are masked tables (received rows are
front-packed per sender block; the validity mask exposes them without a sort).
``broadcast_table`` INPUTS are compacted first — the gathered payload is
reconstructed from per-shard counts alone, a true contiguity boundary;
``shuffle`` inputs may stay masked (invalid rows route to a dropped bucket).
"""
from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from . import tracing
from . import wire as wi
from .table import Table
from .relational import agg_kernel_default, ensure_compact, hash_partition_ids
# imported at module scope (not lazily inside traced code): the kernel module
# materializes constants at import time, which must not happen under a trace
from repro.kernels.radix_hist import ops as _rh_ops

__all__ = [
    "ExchangeStats",
    "pack_columns",
    "unpack_columns",
    "shuffle",
    "broadcast_table",
    "broadcast_table_p2p",
    "partial_to_global",
]


@dataclasses.dataclass
class ExchangeStats:
    """Static (trace-time) descriptor of one exchange — feeds the perf models.

    ``message_bytes``/``total_bytes`` are ACTUAL wire bytes (packed words x 4,
    including the fused counts header row and, in per-column mode, the
    separate metadata round); ``logical_bytes`` is the dtype-true payload
    size per message, so ``logical_bytes / message_bytes`` approaches the
    wire-compression ratio as capacity padding amortizes.  The per-row pair
    (``row_wire_bytes``, ``row_logical_bytes``) is capacity-independent and
    equals the IR-derived static numbers on every backend
    (``planner.static_wire_stats``).
    """
    kind: str                 # "shuffle" | "broadcast" | "broadcast_p2p" | "gather"
    participants: int         # N
    message_bytes: int        # wire bytes per p2p message / per-shard payload
    total_bytes: int          # wire bytes leaving each device
    collectives: int          # number of collective ops issued
    logical_bytes: int = 0    # dtype-true payload bytes per message
    row_wire_bytes: int = 0   # packed row width on the wire
    row_logical_bytes: int = 0  # dtype-true row width
    wire: str = "wide"        # "narrow" | "wide"

    @property
    def compression(self) -> float:
        """Logical-to-wire row compression ratio (>= 1 when narrowing wins)."""
        return self.row_logical_bytes / max(1, self.row_wire_bytes)


# ---------------------------------------------------------------------------
# column packing
# ---------------------------------------------------------------------------

def _table_format(t: Table, bounds: Mapping | None, narrow: bool | None,
                  ) -> wi.WireFormat:
    if narrow is None:
        narrow = wi.wire_default() == "narrow"
    return wi.plan_wire_format(
        t.names, {n: np.dtype(t[n].dtype) for n in t.names},
        bounds=bounds, narrow=narrow)


def pack_columns(t: Table, wire: Mapping | None = None,
                 narrow: bool | None = None,
                 ) -> tuple[jax.Array, wi.WireFormat, jax.Array]:
    """Table columns -> ((capacity, words) int32 buffer, format, overflow).

    ``wire`` maps column names to provable ``(lo, hi)`` bounds (planner
    statistics); ``narrow=None`` follows ``REPRO_WIRE``.  Without bounds the
    layout is the legacy full-width format and overflow is statically False.
    """
    fmt = _table_format(t, wire, narrow)
    buf, overflow = wi.pack_table(t, fmt)
    return buf, fmt, overflow


def unpack_columns(buf: jax.Array, fmt: wi.WireFormat) -> dict[str, jax.Array]:
    return wi.unpack_table(buf, fmt)


# ---------------------------------------------------------------------------
# shuffle
# ---------------------------------------------------------------------------

def _dispatch_offsets(dest: jax.Array, num_partitions: int,
                      use_kernel: bool | None = None):
    """Per-row (destination, slot) for capacity-bounded dispatch.

    Returns (slot, counts): ``slot[i]`` is row i's index within its destination
    bucket, ``counts[d]`` the number of rows headed to d.  Rows are ranked by
    a radix-histogram counting rank (``kernels/radix_hist.counting_rank``:
    one fused Pallas pass — per-block histogram, triangular-matmul exclusive
    rank, running-total carry — or the block-streamed jnp oracle) —
    byte-identical slot assignment to the previous stable destination sort,
    with ZERO sorts.  Destinations may include the drop bucket
    ``num_partitions`` (padding / invalid rows); its rows are ranked too but
    excluded from ``counts``.
    """
    if use_kernel is None:
        use_kernel = agg_kernel_default()
    slot, counts = _rh_ops.counting_rank(dest, num_partitions + 1,
                                         use_kernel=use_kernel)
    return slot, counts[:num_partitions]


@tracing.op_scope(tracing.EXCHANGE)
def shuffle(t: Table, key: jax.Array, axis_name: str, num_partitions: int,
            cap_per_dest: int, packed: bool = True,
            dest_ids: jax.Array | None = None,
            use_kernel: bool | None = None,
            wire: Mapping | None = None, narrow: bool | None = None,
            tamper=None,
            ) -> tuple[Table, jax.Array, jax.Array, jax.Array, ExchangeStats]:
    """Repartition ``t`` by ``hash(key) % N`` across the mesh axis.

    Returns (table, overflowed, corrupt, per-sender recv counts, stats).  The
    output table has capacity ``N * cap_per_dest``; ``overflowed`` is True on
    any device whose bucket exceeded ``cap_per_dest`` (rows are dropped — the
    fault-tolerant runner re-executes with a larger capacity factor, the
    static-shape analogue of re-allocating NCCL receive buffers) OR whose
    narrowed wire lanes saw an out-of-bounds value (re-execution recompiles
    at full width).  In packed mode the per-destination counts ride as a
    header row of the payload buffer, so the whole exchange — size metadata
    included — is ONE ``all_to_all``; each block also carries its integrity
    checksum in the header row, verified on receive into ``corrupt`` (the
    per-column baseline ships unchecked: statically False).  ``tamper``, if
    given, maps the received payload sub-buffer to a corrupted copy (chaos
    injection — applied before verification, so injected flips are caught).
    """
    N, cap = num_partitions, t.capacity
    dest = jnp.where(t.valid_mask(),
                     hash_partition_ids(key, N) if dest_ids is None else dest_ids,
                     N)  # padding rows -> virtual bucket N (dropped)
    slot, counts = _dispatch_offsets(dest, N, use_kernel=use_kernel)
    overflow = jnp.any(counts > cap_per_dest)
    counts_capped = jnp.minimum(counts, cap_per_dest).astype(jnp.int32)

    if packed:
        # rows scatter into per-destination blocks of cap_per_dest+1 rows:
        # row 0 is the counts header (word 0 = sender's row count for that
        # destination), rows 1.. are the payload — one collective total.
        blk = cap_per_dest + 1
        flat_idx = dest * blk + 1 + jnp.minimum(slot, cap_per_dest - 1)
        keep = (slot < cap_per_dest) & (dest < N)
        flat_idx = jnp.where(keep, flat_idx, N * blk)  # OOB -> dropped
        buf, fmt, ov_wire = pack_columns(t, wire=wire, narrow=narrow)
        overflow = overflow | ov_wire
        send = jnp.zeros((N * blk, fmt.words), jnp.int32) \
            .at[flat_idx].set(buf, mode="drop") \
            .reshape(N, blk, fmt.words)
        cmode = wi.header_mode(fmt.words, cap_per_dest)
        csum = jax.vmap(wi.payload_checksum)(send[:, 1:, :])
        send = send.at[:, 0, 0].set(
            wi.encode_header_word0(counts_capped, csum, cmode))
        if cmode == "word":
            send = send.at[:, 0, 1].set(
                wi.encode_checksum_word(counts_capped, csum))
        recv = jax.lax.all_to_all(send, axis_name, 0, 0)
        if tamper is not None:
            recv = recv.at[:, 1:, :].set(tamper(recv[:, 1:, :]))
        recv_counts = wi.decode_header_word0(recv[:, 0, 0], cmode)
        corrupt = jnp.any(jax.vmap(
            lambda h, p: wi.verify_block_checksum(h, p, cmode))(
                recv[:, 0, :], recv[:, 1:, :]))
        cols = unpack_columns(recv[:, 1:, :].reshape(N * cap_per_dest,
                                                     fmt.words), fmt)
        n_coll = 1
        words = fmt.words
        msg_rows = blk
        row_wire, row_logical = fmt.row_wire_bytes, fmt.row_logical_bytes
        wire_tag = "narrow" if fmt.narrow else "wide"
    else:  # paper-faithful: one collective per column + the metadata round
        corrupt = jnp.asarray(False)   # §2.3 baseline ships unchecked
        flat_idx = dest * cap_per_dest + jnp.minimum(slot, cap_per_dest - 1)
        keep = (slot < cap_per_dest) & (dest < N)
        flat_idx = jnp.where(keep, flat_idx, N * cap_per_dest)

        recv_counts = jax.lax.all_to_all(
            counts_capped.reshape(N, 1), axis_name, 0, 0)[:, 0]

        def _exchange(col2d: jax.Array) -> jax.Array:
            send = jnp.zeros((N * cap_per_dest, col2d.shape[1]), col2d.dtype) \
                .at[flat_idx].set(col2d, mode="drop") \
                .reshape(N, cap_per_dest, col2d.shape[1])
            return jax.lax.all_to_all(send, axis_name, 0, 0).reshape(
                N * cap_per_dest, col2d.shape[1])

        cols = {}
        words = 0
        for name in t.names:
            part = wi.to_words(t[name])
            got = _exchange(part)
            cols[name] = wi.from_words(got, t[name].dtype)
            words += part.shape[1]
        n_coll = len(t.names) + 1              # + metadata round
        msg_rows = cap_per_dest
        row_wire = words * 4
        row_logical = sum(np.dtype(t[n].dtype).itemsize for n in t.names)
        wire_tag = "wide"

    # received rows are front-packed within each per-sender block; expose them
    # through the deferred-compaction mask instead of paying a full sort here
    valid = (jnp.arange(N * cap_per_dest) % cap_per_dest) < \
        jnp.repeat(recv_counts, cap_per_dest)
    out = Table(cols, recv_counts.sum().astype(jnp.int32), valid)

    msg = msg_rows * words * 4 + (4 if not packed else 0)  # + metadata ints
    stats = ExchangeStats(
        kind="shuffle", participants=N,
        message_bytes=msg,
        total_bytes=N * msg,
        collectives=n_coll,
        logical_bytes=cap_per_dest * row_logical,
        row_wire_bytes=row_wire,
        row_logical_bytes=row_logical,
        wire=wire_tag,
    )
    return out, overflow, corrupt, recv_counts, stats


# ---------------------------------------------------------------------------
# broadcast
# ---------------------------------------------------------------------------

@tracing.op_scope(tracing.EXCHANGE)
def broadcast_table(t: Table, axis_name: str, num_partitions: int,
                    packed: bool = True, wire: Mapping | None = None,
                    narrow: bool | None = None, tamper=None,
                    ) -> tuple[Table, jax.Array, jax.Array, ExchangeStats]:
    """Replicate a distributed table on every device (paper Fig. 3).

    all_gather == the ring broadcast of Eq. 1 on the ICI torus: every device
    streams its shard around the ring; N-1 hops of S/N bytes each.  Returns
    (table, overflow, corrupt, stats); in packed mode the per-shard row count
    AND payload checksum ride as a header row of the gathered buffer (ONE
    collective), ``overflow`` reports narrowed-lane range violations (always
    False when wide) and ``corrupt`` reports a per-shard checksum mismatch
    after the optional ``tamper`` hook (per-column mode: statically False).
    """
    # the gathered payload is reconstructed from per-shard counts alone, so the
    # payload must be front-compacted — this is a true contiguity boundary
    t = ensure_compact(t)
    N, cap = num_partitions, t.capacity
    overflow = jnp.asarray(False)
    corrupt = jnp.asarray(False)
    if packed:
        buf, fmt, overflow = pack_columns(t, wire=wire, narrow=narrow)
        cmode = wi.header_mode(fmt.words, cap)
        csum = wi.payload_checksum(buf)
        count32 = t.count.astype(jnp.int32)
        hdr = jnp.zeros((1, fmt.words), jnp.int32) \
            .at[0, 0].set(wi.encode_header_word0(count32, csum, cmode))
        if cmode == "word":
            hdr = hdr.at[0, 1].set(wi.encode_checksum_word(count32, csum))
        recv = jax.lax.all_gather(jnp.concatenate([hdr, buf]), axis_name,
                                  tiled=True).reshape(N, cap + 1, fmt.words)
        if tamper is not None:
            recv = recv.at[:, 1:, :].set(tamper(recv[:, 1:, :]))
        counts = wi.decode_header_word0(recv[:, 0, 0], cmode)
        corrupt = jnp.any(jax.vmap(
            lambda h, p: wi.verify_block_checksum(h, p, cmode))(
                recv[:, 0, :], recv[:, 1:, :]))
        cols = unpack_columns(recv[:, 1:, :].reshape(N * cap, fmt.words), fmt)
        n_coll, words, msg_rows = 1, fmt.words, cap + 1
        row_wire, row_logical = fmt.row_wire_bytes, fmt.row_logical_bytes
        wire_tag = "narrow" if fmt.narrow else "wide"
    else:
        counts = jax.lax.all_gather(t.count.reshape(1), axis_name, tiled=True)
        cols, words = {}, 0
        for name in t.names:
            part = wi.to_words(t[name])
            got = jax.lax.all_gather(part, axis_name, tiled=True)
            cols[name] = wi.from_words(got, t[name].dtype)
            words += part.shape[1]
        n_coll, msg_rows = len(t.names) + 1, cap
        row_wire = words * 4
        row_logical = sum(np.dtype(t[n].dtype).itemsize for n in t.names)
        wire_tag = "wide"

    valid = (jnp.arange(N * cap) % cap) < jnp.repeat(counts, cap)
    out = Table(cols, counts.sum().astype(jnp.int32), valid)
    msg = msg_rows * words * 4 + (4 if not packed else 0)
    stats = ExchangeStats(kind="broadcast", participants=N,
                          message_bytes=msg,
                          total_bytes=msg * (N - 1),
                          collectives=n_coll,
                          logical_bytes=cap * row_logical,
                          row_wire_bytes=row_wire,
                          row_logical_bytes=row_logical,
                          wire=wire_tag)
    return out, overflow, corrupt, stats


@tracing.op_scope(tracing.EXCHANGE)
def broadcast_table_p2p(t: Table, axis_name: str, num_partitions: int,
                        ) -> tuple[Table, ExchangeStats]:
    """§7.1 baseline: emulate broadcast with N-1 p2p ring forwards of the FULL
    buffer — each shard transits every link once per hop instead of being
    pipelined, duplicating inter-node traffic exactly as the paper describes.
    Shows up in HLO as N-1 collective-permutes of the full shard.  Stays on
    the WIDE wire format deliberately: it is the paper's unoptimized baseline."""
    t = ensure_compact(t)
    N, cap = num_partitions, t.capacity
    buf, fmt, _ = pack_columns(t, narrow=False)
    counts = jax.lax.all_gather(t.count.reshape(1), axis_name, tiled=True)
    parts = [buf]
    cur = buf
    perm = [(i, (i + 1) % N) for i in range(N)]
    for _ in range(N - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        parts.append(cur)
    me = jax.lax.axis_index(axis_name)
    # parts[s] came from device (me - s) % N; reorder to device order 0..N-1
    recv = jnp.stack(parts)                       # (N, cap, words)
    src = (me - jnp.arange(N)) % N
    order = jnp.zeros(N, jnp.int32).at[src].set(jnp.arange(N, dtype=jnp.int32))
    recv = recv[order].reshape(N * cap, -1)
    cols = unpack_columns(recv, fmt)
    valid = (jnp.arange(N * cap) % cap) < jnp.repeat(counts, cap)
    out = Table(cols, counts.sum().astype(jnp.int32), valid)
    stats = ExchangeStats(kind="broadcast_p2p", participants=N,
                          message_bytes=cap * fmt.words * 4 + 4,
                          total_bytes=(cap * fmt.words * 4 + 4) * (N - 1),
                          collectives=N,  # N-1 permutes + counts gather
                          logical_bytes=cap * fmt.row_logical_bytes,
                          row_wire_bytes=fmt.row_wire_bytes,
                          row_logical_bytes=fmt.row_logical_bytes,
                          wire="wide")
    return out, stats


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

@tracing.op_scope(tracing.EXCHANGE)
def partial_to_global(partials: dict[str, jax.Array], ops: dict[str, str],
                      axis_name: str) -> dict[str, jax.Array]:
    """ncclAllReduce equivalent for final scalar aggregation.

    min/max gather the partials and reduce them locally: TPU lowers only a
    sum all-reduce of float64, and the gather is exact for every dtype."""
    out = {}
    for k, v in partials.items():
        op = ops[k]
        if op in ("sum", "count"):
            out[k] = jax.lax.psum(v, axis_name)
        elif op in ("min", "max"):
            got = jax.lax.all_gather(v, axis_name)
            out[k] = (jnp.min if op == "min" else jnp.max)(got, axis=0)
        else:
            raise ValueError(op)
    return out
