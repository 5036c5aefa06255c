"""The network operator: hash-partition + AllToAll shuffle (Cylon §II-B/C, Fig. 3).

This is the paper's single network primitive ("Initially we have implemented
the All to All network operator which is widely required when implementing
the distributed counterparts of the local operators"). Every distributed
relational operator — and, in this framework, MoE expert dispatch — is
``local prep -> repartition -> local op``.

MPI ``AllToAllv`` (variable counts) has no dense-collective equivalent on a
TPU mesh, so we adapt: each shard packs rows into ``num_partitions`` equal
``bucket_capacity`` send slots (grouped with a stable sort — dense, vectorized)
and exchanges them with ``jax.lax.all_to_all``. Skew beyond
``bucket_capacity`` is *counted and surfaced* (``overflow``) rather than
silently dropped being undetectable — the production recourse is re-running
with a bigger capacity, mirroring Cylon's memory-budget failure mode.

The exchange itself is **staged** (:func:`staged_all_to_all`): the
``(p, bucket_capacity)`` send buckets split into ``S`` chunks along the
capacity axis, one collective per chunk, so XLA's scheduler can overlap
chunk i+1's gather/pack and chunk i-1's unpack with chunk i's wire time
inside the one fused shard_map program. Chunks are written back into the
same ``(p, bucket)`` slots a monolithic exchange fills, so every staging
(and the ``ppermute``-ring strategy, ``shuffle_mode="ring"``) is
bit-identical to ``S=1`` — same recv buffers, same overflow counts, same
row order after ``compact``. The per-bucket send counts ride *inside* the
first chunk of the first 4-byte column (bitcast into a prepended capacity
slot), folding the old separate ``recv_counts`` collective into the data
exchange — one fewer collective per shuffle.

Runs inside ``shard_map`` (BSP lockstep = SPMD).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults as FLT
from repro.core.table import Table
from repro.core.ops_local import compact
from repro.kernels import ops as kops


class ShuffleStats(NamedTuple):
    overflow: jax.Array  # int32 scalar: rows dropped on THIS shard's sends
    received: jax.Array  # int32 scalar: valid rows received


class Partitioning(NamedTuple):
    """Static placement metadata: rows live on shard ``hash(keys) % n``.

    Tagged onto a ``DistTable`` (and tracked through the plan optimizer) so a
    downstream join/groupby on the same key columns, seed, and modulus can
    *elide* its AllToAll entirely — equal keys are already colocated. The
    tag is exact, not advisory: it is only attached to tables produced by a
    hash repartition (or an operator that provably preserves one).
    """

    keys: tuple[str, ...]   # key columns, in the order they were hashed
    num_partitions: int     # the modulus (== mesh axis size when created)
    seed: int               # murmur3 seed of the partitioning hash


@dataclasses.dataclass(frozen=True)
class RangePartitioning:
    """Static placement metadata for range-partitioned tables (sort output).

    Rows live on shard ``f(keys)`` for a *monotone lexicographic* placement
    function f: shard i's key tuples are all <= shard i+1's, and equal key
    tuples are colocated (``dist_sort``'s splitter assignment is a pure
    function of the key tuple). Unlike the hash tag the splitters are
    data-dependent, so the tag does not name them — downstream operators
    that must co-place a second table re-derive the shard boundaries from
    the tagged table itself (per-shard key maxima, an all_gather of p
    scalars, not an AllToAll — see ``ops_dist._range_align_pid``).

    ``fingerprint`` is splitter provenance: two tags compare equal (and a
    join may skip BOTH shuffles) only when they provably came from the same
    splitter computation over the same data. Plan-internal tags use the
    canonical form of the producing subtree; materialized DistTables get a
    fresh unique token so tables from different executions never
    false-match. A deliberate dataclass (not NamedTuple): tuple equality
    would let a RangePartitioning compare equal to a hash ``Partitioning``
    with coincident fields.
    """

    keys: tuple[str, ...]   # key columns, lexicographic significance order
    num_partitions: int     # number of range buckets (== mesh axis size)
    fingerprint: object     # hashable provenance token, or None (unknown)


_FINGERPRINTS = itertools.count()


def fresh_range_fingerprint() -> tuple:
    """Unique provenance token for a materialized range-partitioned table."""
    return ("table", next(_FINGERPRINTS))


def range_prefix_matches(part, keys: tuple[str, ...]) -> bool:
    """True when ``part`` is a RangePartitioning whose key columns are a
    prefix of ``keys`` — the placement is then a function of a prefix of
    the operator's keys, so equal operator-key tuples are colocated."""
    return (isinstance(part, RangePartitioning)
            and len(part.keys) <= len(keys)
            and part.keys == tuple(keys[:len(part.keys)]))


def zero_shuffle_stats() -> ShuffleStats:
    """Stats for an elided shuffle: nothing sent, nothing dropped."""
    return ShuffleStats(overflow=jnp.zeros((), jnp.int32),
                        received=jnp.zeros((), jnp.int32))


def pack_by_partition(part_id: jax.Array, num_partitions: int,
                      bucket_capacity: int):
    """Group rows into equal-capacity per-partition send slots.

    part_id: (n,) int32 destination in [0, num_partitions); -1 = skip.
    Returns (send_idx (num_partitions, bucket_capacity) int32 with -1 for
    empty slots, hist (num_partitions,) int32 true per-partition counts).

    This is the shared dense-packing primitive behind BOTH the relational
    shuffle (`repartition`) and MoE expert dispatch (`models/moe.py`) —
    the paper's AllToAll network operator reused for token routing
    (DESIGN.md §2, level-2).
    """
    (n,) = part_id.shape
    if n == 0:
        # clip(off + j, 0, n - 1) has an invalid upper bound at n == 0 and
        # order is empty — nothing to pack, every slot is vacant
        return (jnp.full((num_partitions, bucket_capacity), -1, jnp.int32),
                jnp.zeros((num_partitions,), jnp.int32))
    pid_sort = jnp.where(part_id >= 0, part_id, num_partitions)
    order = jnp.argsort(pid_sort, stable=True)
    hist = kops.bucket_histogram(part_id, num_partitions)
    off = jnp.cumsum(hist) - hist
    j = jnp.arange(bucket_capacity)[None, :]
    src = jnp.clip(off[:, None] + j, 0, n - 1)
    ok = j < hist[:, None]
    return jnp.where(ok, order[src], -1), hist


def _chunk_bounds(width: int, stages: int) -> list[tuple[int, int]]:
    """Split ``[0, width)`` into ~``stages`` contiguous chunks.

    Clamps: ``stages <= 1`` (or ``width <= 1``) is one chunk, ``stages >
    width`` degrades to one slot per chunk, and a non-divisible width puts
    the remainder in the last chunk. Empty list when ``width == 0``.
    """
    if width <= 0:
        return []
    from repro.utils import ceil_div

    step = ceil_div(width, max(1, min(int(stages), width)))
    return [(lo, min(lo + step, width)) for lo in range(0, width, step)]


def _ring_exchange(buf: jax.Array, axis_name: str) -> jax.Array:
    """AllToAll via a ``ppermute`` ring: p-1 point-to-point steps.

    Step k sends this shard's bucket for destination ``(i + k) % p`` along
    the static permutation ``s -> (s + k) % p``; the receiver stores it at
    recv slot ``(i - k) % p`` — element-for-element the placement
    ``jax.lax.all_to_all(split=0, concat=0)`` produces (k = 0 is the local
    bucket, no collective). A comparison strategy for the staged dense
    collective: maximally decomposed, so `stages` does not subdivide it.
    """
    p = jax.lax.axis_size(axis_name)
    if p == 1:
        return buf
    idx = jax.lax.axis_index(axis_name)
    out = jnp.zeros_like(buf)
    for k in range(p):
        send_slot = jax.lax.rem(idx + k, p)
        chunk = jax.lax.dynamic_index_in_dim(buf, send_slot, axis=0,
                                             keepdims=True)
        if k:
            chunk = jax.lax.ppermute(
                chunk, axis_name, [(s, (s + k) % p) for s in range(p)])
        recv_slot = jax.lax.rem(idx - k + p, p)
        out = jax.lax.dynamic_update_index_in_dim(out, chunk, recv_slot,
                                                  axis=0)
    return out


def staged_all_to_all(buf: jax.Array, axis_name: str, *, stages: int = 1,
                      shuffle_mode: str = "alltoall") -> jax.Array:
    """Exchange ``(p, width, *rest)`` send buckets, optionally pipelined.

    ``stages > 1`` splits the width (capacity) axis into that many chunks
    and issues one ``all_to_all`` per chunk; each chunk lands in the same
    ``(source, slot)`` position the monolithic collective fills, so the
    result is bit-identical for every staging while XLA overlaps one
    chunk's wire time with its neighbours' pack/unpack compute.
    ``shuffle_mode="ring"`` swaps in :func:`_ring_exchange` (p-1 ppermute
    steps) — also bit-identical, also already decomposed, so ``stages`` is
    ignored there.
    """
    if shuffle_mode == "ring":
        return _ring_exchange(buf, axis_name)
    if shuffle_mode != "alltoall":
        raise ValueError(f"unknown shuffle_mode: {shuffle_mode!r}")
    bounds = _chunk_bounds(buf.shape[1], stages)
    if len(bounds) <= 1:
        return jax.lax.all_to_all(buf, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)
    return jnp.concatenate(
        [jax.lax.all_to_all(buf[:, lo:hi], axis_name, split_axis=0,
                            concat_axis=0, tiled=True) for lo, hi in bounds],
        axis=1)


def _poison_chunk(recv: jax.Array, width: int) -> jax.Array:
    """Overwrite the first ``width`` received capacity slots with the NaN
    bit pattern — the ``shuffle.chunk`` garble/drop fault. Floats become
    NaN (caught by the finalize NaN scan); a 4-byte carrier's bitcast
    counts decode to an absurd row count (caught by the received-rows
    invariant). Either way validation quarantines the run."""
    if jnp.issubdtype(recv.dtype, jnp.floating):
        bad = jnp.asarray(jnp.nan, recv.dtype)
    elif recv.dtype.itemsize == 4:
        # the float32 quiet-NaN bit pattern, so bitcast counts explode
        bad = jnp.asarray(np.float32(np.nan).view(np.int32), recv.dtype)
    else:
        bad = jnp.asarray(jnp.iinfo(recv.dtype).max, recv.dtype)
    return recv.at[:, :width].set(bad)


def _shuffle_fault(bucket_capacity: int, stages: int,
                   shuffle_mode: str) -> FLT.FaultPlan | None:
    """Consult the ``shuffle.chunk`` site for one exchange. Only a
    pipelined exchange (staged chunks or the ppermute ring) is eligible —
    the fault models pipelining bugs, so the monolithic-AllToAll recovery
    rung provably avoids it. Raise-mode aborts the trace here; garble
    mode returns the plan for :func:`repartition` to poison a received
    chunk with."""
    staged = (shuffle_mode == "ring"
              or len(_chunk_bounds(bucket_capacity, stages)) > 1)
    if not staged:
        return None
    fp = FLT.check("shuffle.chunk")
    if fp is not None and fp.effective_mode == "raise":
        raise FLT.FaultError("shuffle.chunk",
                             f"stages={stages} mode={shuffle_mode}")
    return fp


def _counts_carrier(table: Table) -> str | None:
    """The column whose exchange carries the per-bucket send counts: the
    first (sorted) 4-byte integer column — the int32 counts bitcast
    losslessly into its dtype and ride a prepended capacity slot of its
    FIRST chunk, so no separate counts collective is needed. Never a float
    column: a count below 2^23 bitcasts to a subnormal float, which the TPU
    flushes to zero, dropping the whole bucket. None when no column
    qualifies (the separate-collective fallback)."""
    for name in table.column_names:
        dtype = table.columns[name].dtype
        if dtype.itemsize == 4 and jnp.issubdtype(dtype, jnp.integer):
            return name
    return None


def repartition(
    table: Table,
    part_id: jax.Array,
    *,
    axis_name: str,
    bucket_capacity: int,
    stages: int = 1,
    shuffle_mode: str = "alltoall",
) -> tuple[Table, ShuffleStats]:
    """Send each valid row to the shard named by ``part_id`` (int32, -1=invalid).

    Returns the received table (capacity = num_shards * bucket_capacity,
    valid rows front-compacted) and shuffle stats. ``stages`` pipelines the
    exchange (see :func:`staged_all_to_all`); every ``(stages,
    shuffle_mode)`` is bit-identical — same recv layout, same overflow
    accounting, same compacted row order.
    """
    p = jax.lax.axis_size(axis_name)
    c = table.capacity
    cb = bucket_capacity
    valid = table.valid_mask()

    # group rows by destination: stable sort on (pid, original order)
    send_idx, hist = pack_by_partition(
        jnp.where(valid, part_id, -1), p, cb)  # (p, cb)
    sent = jnp.minimum(hist, cb).astype(jnp.int32)
    carrier = _counts_carrier(table)
    fault = _shuffle_fault(cb, stages, shuffle_mode)
    # garble the carrier (or the only exchanged column when none): its
    # first received chunk — counts slot included — turns to NaN-pattern
    # bytes, exactly what a lost/corrupt pipeline chunk looks like
    garble_col = carrier if carrier is not None else table.column_names[0]

    recv_cols = {}
    recv_counts = None
    for name, col in table.columns.items():
        rest = col.shape[1:]
        if c == 0:  # empty table: nothing to gather, all slots vacant
            buf = jnp.zeros((p, cb) + rest, col.dtype)
        else:
            buf = col[jnp.clip(send_idx, 0, c - 1)]  # (p, cb, *rest)
            sel = send_idx.reshape(send_idx.shape + (1,) * (col.ndim - 1)) >= 0
            buf = jnp.where(sel, buf, jnp.zeros_like(buf))
        if name == carrier:
            # counts fold: bitcast the (p,) int32 sent counts into this
            # column's dtype and PREPEND them as capacity slot 0, so they
            # ride the first chunk of the staged exchange; the collective
            # moves bytes verbatim, so the round trip is lossless
            cnt = jax.lax.bitcast_convert_type(sent, col.dtype)
            if rest:
                meta = jnp.zeros((p, int(np.prod(rest))), col.dtype)
                meta = meta.at[:, 0].set(cnt).reshape((p, 1) + rest)
            else:
                meta = cnt[:, None]
            buf = jnp.concatenate([meta, buf], axis=1)  # (p, cb+1, *rest)
        recv = staged_all_to_all(buf, axis_name, stages=stages,
                                 shuffle_mode=shuffle_mode)
        if fault is not None and name == garble_col:
            bounds = _chunk_bounds(buf.shape[1], stages)
            width = bounds[0][1] if shuffle_mode != "ring" else buf.shape[1]
            recv = _poison_chunk(recv, width)
        if name == carrier:
            meta_r = recv[:, 0]
            if rest:
                meta_r = meta_r.reshape(p, -1)[:, 0]
            recv_counts = jax.lax.bitcast_convert_type(meta_r, jnp.int32)
            recv = recv[:, 1:]
        recv_cols[name] = recv.reshape((p * cb,) + rest)

    if recv_counts is None:  # no 4-byte column: separate counts collective
        recv_counts = staged_all_to_all(
            sent.reshape(p, 1), axis_name,
            shuffle_mode=shuffle_mode).reshape(p)

    recv_valid = (jnp.arange(cb)[None, :] < recv_counts[:, None]).reshape(p * cb)
    out = compact(Table(recv_cols, jnp.asarray(p * cb, jnp.int32)), recv_valid)
    stats = ShuffleStats(
        overflow=jnp.sum(jnp.maximum(hist - cb, 0)).astype(jnp.int32),
        received=jnp.sum(recv_counts).astype(jnp.int32),
    )
    return out, stats


def default_bucket_capacity(capacity: int, num_shards: int,
                            slack: float | None = None) -> int:
    """Per-destination slot budget: even split x slack for skew.

    ``slack=None`` uses :data:`repro.core.stats.FALLBACK_SLACK` — the one
    documented no-statistics constant. The plan optimizer replaces this
    sizing entirely when table statistics are available (see
    ``repro.core.stats`` and the cost pass in ``repro.core.plan``).
    """
    from repro.core.stats import FALLBACK_SLACK
    from repro.utils import ceil_div

    if slack is None:
        slack = FALLBACK_SLACK
    return max(1, ceil_div(int(capacity * slack), num_shards))
