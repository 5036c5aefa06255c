"""Distributed relational operators (Cylon Fig. 3): local ops ∘ shuffle.

Each function here runs **inside** ``shard_map`` over the shuffle axis —
the BSP worker program of the paper. ``repro.core.context.DistContext``
provides the user-facing wrappers that build the shard_map/jit around them,
and ``repro.core.plan`` fuses whole chains of them into one body.

Composition table (paper §II-B):
  select/project      : pleasingly parallel, no network
  join                : hash_partition(key) -> AllToAll -> local join
  union/intersect/diff: hash_partition(whole row) -> AllToAll -> local op
  sort (global)       : sample splitters -> range partition -> local sort

Shuffle elision: every operator takes ``skip_*_shuffle`` flags. When the
plan optimizer proves an input is already hash-partitioned on the operator's
keys (same seed, same modulus — the :class:`~repro.core.repartition.
Partitioning` tag), the AllToAll is skipped and a zero :class:`ShuffleStats`
is emitted in its place, so stats shapes stay stable either way. The
optional ``report`` list collects one static record per potential shuffle
(bucket, bytes/row, dense wire bytes) at trace time — the fused-vs-eager
accounting surfaced by ``benchmarks/bench_plan``.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import ops_agg as A
from repro.core import ops_local as L
from repro.core.repartition import (ShuffleStats, _counts_carrier,
                                    repartition, zero_shuffle_stats)
from repro.core.table import Table


#: the ``jax.named_scope`` of every exchange: destination ids (hash or
#: range), pack, the AllToAll stages and unpack. It nests inside the
#: operator's scope (``plan.OPERATOR_SCOPES``) and takes the exchange's
#: device time away from that operator.
EXCHANGE_SCOPE = "engine.exchange"


def _row_pid(table: Table, key_columns: Sequence[str], p: int, seed: int):
    pid, _ = L.hash_partition(table, key_columns, p, seed=seed)
    return pid


def _row_bytes(table: Table) -> int:
    """Bytes per row of the dense wire format (all columns, all payload)."""
    total = 0
    for v in table.columns.values():
        n = 1
        for d in v.shape[1:]:
            n *= d
        total += n * v.dtype.itemsize
    return total


def _shuffle(table: Table, keys: Sequence[str], *, axis_name: str,
             bucket_capacity: int, seed: int, skip: bool = False,
             report: list | None = None, label: str = "shuffle",
             pid=None, stages: int | None = None,
             shuffle_mode: str = "alltoall") -> tuple[Table, ShuffleStats]:
    """Hash-partition + AllToAll, or the elided identity when ``skip``.

    One record per call lands in ``report`` (at trace time): the dense
    AllToAll ships ``p^2 * bucket * row_bytes`` regardless of row validity,
    so the wire volume is static — 0 when the shuffle is elided, and the
    same for every ``stages`` (staging re-chunks the exchange, it never
    changes what crosses the wire). ``stages=None`` auto-sizes from the
    wire-byte estimate (:func:`repro.core.stats.pick_stages`).
    """
    from repro.core import stats as S

    p = jax.lax.axis_size(axis_name)
    rb = _row_bytes(table)
    if stages is None and not skip:
        stages = S.pick_stages(p * p * bucket_capacity * rb, bucket_capacity)
    if report is not None:
        report.append({
            "op": label, "elided": bool(skip), "row_bytes": rb,
            "bucket": 0 if skip else bucket_capacity,
            "wire_bytes": 0 if skip else p * p * bucket_capacity * rb,
            "stages": 0 if skip else stages, "mode": shuffle_mode,
            # enough shape detail that verify.expected_collectives can
            # reconstruct the per-column exchange decomposition statically
            "columns": len(table.columns),
            "carrier": _counts_carrier(table) is not None,
        })
    if skip:
        return table, zero_shuffle_stats()
    with jax.named_scope(EXCHANGE_SCOPE):
        if pid is None:
            pid = _row_pid(table, list(keys), p, seed)
        return repartition(table, pid, axis_name=axis_name,
                           bucket_capacity=bucket_capacity, stages=stages,
                           shuffle_mode=shuffle_mode)


def dist_repartition_by(table: Table, keys: Sequence[str] | str, *,
                        axis_name: str, bucket_capacity: int, seed: int = 7,
                        skip_shuffle: bool = False, report: list | None = None,
                        stages: int | None = None,
                        shuffle_mode: str = "alltoall"):
    """Explicit hash repartition — pre-partition once, elide shuffles later.

    The caller (DistContext / LazyFrame) tags the result with the matching
    :class:`Partitioning`, making every subsequent join/groupby on ``keys``
    with the same seed a shuffle-free local operator.
    """
    keys_l = [keys] if isinstance(keys, str) else list(keys)
    out, st = _shuffle(table, keys_l, axis_name=axis_name,
                       bucket_capacity=bucket_capacity, seed=seed,
                       skip=skip_shuffle, report=report, label="repartition",
                       stages=stages, shuffle_mode=shuffle_mode)
    return out, (st,)


def _lex_cascade_pid(splitters, row_keys, capacity: int, *,
                     strict: bool) -> jax.Array:
    """pid[r] = #{splitter tuples lexicographically < row r} (strict) or
    <= (non-strict), via a comparison cascade over the key columns —
    sidesteps packing multi-key tuples into one wide integer (no uint64
    without x64 on this stack). The single shared kernel behind BOTH the
    sort's splitter assignment and the join's range alignment: the two
    placements must mirror each other exactly.
    """
    m = splitters[0].shape[0]
    lt = jnp.zeros((m, capacity), bool)
    eq = jnp.ones((m, capacity), bool)
    for s, r in zip(splitters, row_keys):
        s2, r2 = s[:, None], r[None, :]
        lt = lt | (eq & (s2 < r2))
        eq = eq & (s2 == r2)
    le = lt if strict else lt | eq
    return jnp.sum(le.astype(jnp.int32), axis=0)


def _lex_max_key_tuple(table: Table, keys: Sequence[str]):
    """This shard's lexicographically largest valid key tuple, in the
    order-preserving uint32 space (zeros — the lex minimum — on an empty
    shard)."""
    invalid = (~table.valid_mask()).astype(jnp.int32)
    cols_u = [L.ordered_u32(table.columns[k]) for k in keys]
    out = jax.lax.sort((invalid, *cols_u), num_keys=1 + len(cols_u))
    idx = jnp.maximum(table.row_count - 1, 0)  # valid max sorts to rc-1
    return [jnp.where(table.row_count > 0, c[idx], jnp.uint32(0))
            for c in out[1:]]


def _range_align_pid(table: Table, anchor: Table, keys: Sequence[str], *,
                     axis_name: str) -> jax.Array:
    """Destinations placing ``table``'s rows where ``anchor`` keeps equal
    keys.

    ``anchor`` is range-partitioned on ``keys`` (shard key ranges disjoint
    and ordered, equal tuples colocated — the RangePartitioning contract).
    The boundaries are re-derived from the data: boundary i = the running
    lexicographic max of shards 0..i's key tuples (an all_gather of p
    scalars per key column — no AllToAll), and a row goes to
    ``#{boundary < row}`` — rows equal to shard i's max land on shard i,
    rows beyond the global max land on the last shard (where, for a join,
    they meet no anchor rows anyway).
    """
    p = jax.lax.axis_size(axis_name)
    c = table.capacity
    local_max = _lex_max_key_tuple(anchor, keys)
    gathered = [jax.lax.all_gather(m, axis_name) for m in local_max]  # (p,)

    def lex_gt(a, b):  # tuple a > tuple b
        gt = jnp.zeros((), bool)
        eq = jnp.ones((), bool)
        for x, y in zip(a, b):
            gt = gt | (eq & (x > y))
            eq = eq & (x == y)
        return gt

    # running lex-max over shards (p is small and static): empty shards
    # inherit the previous boundary, keeping the boundary sequence monotone
    carry = tuple(col[0] for col in gathered)
    bounds = [carry]
    for i in range(1, p - 1):
        cand = tuple(col[i] for col in gathered)
        take = lex_gt(cand, carry)
        carry = tuple(jnp.where(take, x, y) for x, y in zip(cand, carry))
        bounds.append(carry)
    splitters = [jnp.stack([b[j] for b in bounds])
                 for j in range(len(keys))]  # each (p-1,)

    row_keys = [L.ordered_u32(table.columns[k]) for k in keys]
    pid = _lex_cascade_pid(splitters, row_keys, c, strict=True)
    return jnp.where(table.valid_mask(), pid, -1)


def dist_join(
    left: Table,
    right: Table,
    on: Sequence[str] | str,
    *,
    axis_name: str,
    bucket_capacity: int,
    how: str = "inner",
    algorithm: str = "sort",
    out_capacity: int | None = None,
    seed: int = 7,
    shuffle_seed: int | None = None,
    skip_left_shuffle: bool = False,
    skip_right_shuffle: bool = False,
    align: str | None = None,
    align_keys: Sequence[str] | None = None,
    count_truncation: bool = False,
    report: list | None = None,
    stages: int | None = None,
    shuffle_mode: str = "alltoall",
):
    """Distributed join = shuffle both sides by key hash, then local join.

    Rows with equal keys land on the same shard (same hash, same modulus),
    so the local join of the repartitioned tables is exact. A side whose
    ``skip_*_shuffle`` flag is set is trusted to already be partitioned on
    ``on`` with ``shuffle_seed`` — the co-partitioned fast path.

    ``align``: 'left' or 'right' names a side that is RANGE-partitioned on
    ``align_keys`` (a prefix of ``on`` — e.g. it just came out of
    ``dist_sort``). That side keeps its placement (its skip flag is set by
    the optimizer) and the *other* side is range-partitioned to match,
    using boundaries re-derived from the anchored side's data — one
    AllToAll for the whole join instead of two, and the sort's paid-for
    range placement survives into the join output.

    ``count_truncation``: fold the local join's ``out_capacity``
    truncation count into the right-side ShuffleStats overflow (stats
    pytree shape unchanged). Set by the plan executor whenever the cost
    model sized ``out_capacity`` from a cardinality *estimate*, so an
    underestimate triggers the overflow-retry path instead of silently
    returning a short result.
    """
    on_l = [on] if isinstance(on, str) else list(on)
    ps = seed if shuffle_seed is None else shuffle_seed
    lpid = rpid = None
    with jax.named_scope(EXCHANGE_SCOPE):
        if align == "left":
            rpid = _range_align_pid(right, left, list(align_keys),
                                    axis_name=axis_name)
        elif align == "right":
            lpid = _range_align_pid(left, right, list(align_keys),
                                    axis_name=axis_name)
    left2, st_l = _shuffle(left, on_l, axis_name=axis_name,
                           bucket_capacity=bucket_capacity, seed=ps,
                           skip=skip_left_shuffle, report=report,
                           label="join.left", pid=lpid, stages=stages,
                           shuffle_mode=shuffle_mode)
    right2, st_r = _shuffle(right, on_l, axis_name=axis_name,
                            bucket_capacity=bucket_capacity, seed=ps,
                            skip=skip_right_shuffle, report=report,
                            label="join.right", pid=rpid, stages=stages,
                            shuffle_mode=shuffle_mode)
    if count_truncation:
        out, trunc = L.join(left2, right2, on_l, how=how,
                            algorithm=algorithm, out_capacity=out_capacity,
                            seed=seed + 1, with_overflow=True)
        st_r = st_r._replace(overflow=st_r.overflow + trunc)
    else:
        out = L.join(left2, right2, on_l, how=how, algorithm=algorithm,
                     out_capacity=out_capacity, seed=seed + 1)
    return out, (st_l, st_r)


def dist_limit(table: Table, n: int, *, axis_name: str,
               report: list | None = None):
    """True global head-n: counts prefix-scan -> per-shard take quota.

    Shard i takes ``clip(n - rows_before_i, 0, rows_i)`` of its (front-
    compacted) rows, where ``rows_before_i`` comes from an all_gather of
    the per-shard valid counts — one int32 per shard on the wire, not an
    AllToAll. Concatenating shards in order therefore yields exactly the
    first n rows of the global table: head-n in shard order on unordered
    plans, the true global top-n after ``dist_sort`` (whose shards hold
    ordered key ranges). The report record keeps Limit attributed in the
    wire accounting at 0 bytes.
    """
    p = jax.lax.axis_size(axis_name)
    if report is not None:
        report.append({"op": "limit", "elided": True,
                       "row_bytes": _row_bytes(table), "bucket": 0,
                       "wire_bytes": 0})
    if p == 1:
        return L.head(table, n), (zero_shuffle_stats(),)
    idx = jax.lax.axis_index(axis_name)
    counts = jax.lax.all_gather(table.row_count, axis_name)  # (p,)
    before = jnp.sum(jnp.where(jnp.arange(p) < idx, counts, 0))
    quota = jnp.clip(jnp.asarray(n, jnp.int32) - before, 0, table.row_count)
    cap = min(n, table.capacity)
    cols = {k: v[:cap] for k, v in table.columns.items()}
    return Table(cols, quota.astype(jnp.int32)), (zero_shuffle_stats(),)


def _dist_set_op(a: Table, b: Table, op, *, axis_name: str, bucket_capacity: int,
                 seed: int = 7, skip_left_shuffle: bool = False,
                 skip_right_shuffle: bool = False, report: list | None = None,
                 label: str = "set_op", stages: int | None = None,
                 shuffle_mode: str = "alltoall", **kw):
    """Shuffle by whole-row hash (paper §II-B-4) so duplicates colocate."""
    names = a.column_names
    a2, st_a = _shuffle(a, names, axis_name=axis_name,
                        bucket_capacity=bucket_capacity, seed=seed,
                        skip=skip_left_shuffle, report=report,
                        label=f"{label}.left", stages=stages,
                        shuffle_mode=shuffle_mode)
    b2, st_b = _shuffle(b, names, axis_name=axis_name,
                        bucket_capacity=bucket_capacity, seed=seed,
                        skip=skip_right_shuffle, report=report,
                        label=f"{label}.right", stages=stages,
                        shuffle_mode=shuffle_mode)
    return op(a2, b2, **kw), (st_a, st_b)


def dist_union(a: Table, b: Table, **kw):
    return _dist_set_op(a, b, L.union, label="union", **kw)


def dist_intersect(a: Table, b: Table, **kw):
    return _dist_set_op(a, b, L.intersect, label="intersect", **kw)


def dist_difference(a: Table, b: Table, *, mode: str = "symmetric", **kw):
    return _dist_set_op(a, b, lambda x, y: L.difference(x, y, mode=mode),
                        label="difference", **kw)


def dist_distinct(a: Table, *, axis_name: str, bucket_capacity: int,
                  seed: int = 7, skip_shuffle: bool = False,
                  report: list | None = None, stages: int | None = None,
                  shuffle_mode: str = "alltoall"):
    a2, st = _shuffle(a, a.column_names, axis_name=axis_name,
                      bucket_capacity=bucket_capacity, seed=seed,
                      skip=skip_shuffle, report=report, label="distinct",
                      stages=stages, shuffle_mode=shuffle_mode)
    return L.distinct(a2), (st,)


def dist_groupby(
    table: Table,
    keys: Sequence[str] | str,
    aggs,
    *,
    axis_name: str,
    bucket_capacity: int,
    strategy: str = "two_phase",
    partial_capacity: int | None = None,
    out_capacity: int | None = None,
    seed: int = 7,
    shuffle_seed: int | None = None,
    skip_shuffle: bool = False,
    report: list | None = None,
    stages: int | None = None,
    shuffle_mode: str = "alltoall",
):
    """Distributed GroupBy — both strategies of arXiv:2010.14596.

    strategy='shuffle': hash-partition raw rows by key -> AllToAll -> local
      groupby. Shuffle volume is O(rows) — every row crosses the wire.

    strategy='two_phase': local partial_groupby (<= one row per locally
      distinct key) -> hash-partition the *partials* -> AllToAll -> local
      combine + finalize. Shuffle volume is O(shards x cardinality): on
      low-cardinality keys this moves far fewer bytes, and the AllToAll's
      ``bucket_capacity`` can shrink to ~cardinality/shards.

    ``skip_shuffle``: the input is already partitioned on ``keys`` — every
    key lives on exactly one shard, so a plain local groupby IS the global
    result for either strategy (zero wire traffic).

    ``partial_capacity`` optionally trims the phase-1 partial table (must
    bound the per-shard key cardinality; overflow truncates like join).
    Both strategies produce identical results: one global row per key.
    """
    keys_l = [keys] if isinstance(keys, str) else list(keys)
    pairs = A.normalize_aggs(aggs)
    ps = seed if shuffle_seed is None else shuffle_seed
    if skip_shuffle:
        _, st = _shuffle(table, keys_l, axis_name=axis_name,
                         bucket_capacity=bucket_capacity, seed=ps, skip=True,
                         report=report, label=f"groupby.{strategy}",
                         stages=stages, shuffle_mode=shuffle_mode)
        return A.groupby(table, keys_l, pairs, out_capacity=out_capacity), (st,)
    if strategy == "shuffle":
        t2, st = _shuffle(table, keys_l, axis_name=axis_name,
                          bucket_capacity=bucket_capacity, seed=ps,
                          report=report, label="groupby.shuffle",
                          stages=stages, shuffle_mode=shuffle_mode)
        return A.groupby(t2, keys_l, pairs, out_capacity=out_capacity), (st,)
    if strategy == "two_phase":
        part = A.partial_groupby(table, keys_l, pairs,
                                 out_capacity=partial_capacity)
        part2, st = _shuffle(part, keys_l, axis_name=axis_name,
                             bucket_capacity=bucket_capacity, seed=ps,
                             report=report, label="groupby.two_phase",
                             stages=stages, shuffle_mode=shuffle_mode)
        return A.combine_groupby(part2, keys_l, pairs,
                                 out_capacity=out_capacity), (st,)
    raise ValueError(strategy)


def _fold_window_carry(gathered, by, order_by, p: int, k_of):
    """Left-to-right fold of the all-gathered trailing-group summaries.

    ``gathered`` holds every shard's :func:`ops_agg.window_summary` with a
    leading (p,) axis on each leaf. Walking shards in global sort order
    (a static python loop — p is small), the running state describes the
    trailing group of the prefix processed so far; shard i's carry is the
    state BEFORE shard i is folded in. The fold is pure scalar/(K,) math
    on already-local data: the only wire traffic was the p-sized
    all_gather of the summaries — never an AllToAll.
    """
    def at(k):
        return jax.tree.map(lambda x: x[k], gathered)

    tuple_eq = A._tuple_eq  # same comparison the local carry apply uses

    s0 = at(0)
    state = {
        "has": jnp.asarray(False),
        "key": jax.tree.map(jnp.zeros_like, s0["last_by"]),
        "last_order": jax.tree.map(jnp.zeros_like, s0["last_order"]),
        "count": jnp.zeros((), jnp.int32),
        "runs": jnp.zeros((), jnp.int32),
        "run_eq": jnp.zeros((), jnp.int32),
        "sums": jax.tree.map(jnp.zeros_like, s0["sums"]),
        "maxs": jax.tree.map(jnp.zeros_like, s0["maxs"]),
        "lag": jax.tree.map(jnp.zeros_like, s0["lag"]),
    }
    states = [state]
    for k in range(p - 1):
        sk = at(k)
        nonempty = sk["rows"] > 0
        one_group = tuple_eq(sk["first_by"], sk["last_by"])
        cont_group = state["has"] & tuple_eq(sk["first_by"], state["key"])
        # the prefix's trailing group extends through shard k only when
        # shard k is entirely ONE group continuing the carried key —
        # otherwise shard k's own trailing group replaces the state
        combine = nonempty & one_group & cont_group
        cont_run = combine & tuple_eq(sk["first_order"],
                                      state["last_order"])
        run_merge = combine & tuple_eq(sk["last_order"],
                                       state["last_order"])
        new = {
            "has": state["has"] | nonempty,
            "key": dict(sk["last_by"]),
            "last_order": dict(sk["last_order"]),
            "count": jnp.where(combine, state["count"] + sk["count"],
                               sk["count"]),
            "runs": jnp.where(combine,
                              state["runs"] + sk["runs"]
                              - cont_run.astype(jnp.int32), sk["runs"]),
            "run_eq": jnp.where(run_merge, state["run_eq"] + sk["run_eq"],
                                sk["run_eq"]),
            "sums": {n: jnp.where(combine, state["sums"][n] + v, v)
                     for n, v in sk["sums"].items()},
            "maxs": {n: jnp.where(combine, jnp.maximum(state["maxs"][n], v),
                                  v) for n, v in sk["maxs"].items()},
            "lag": {},
        }
        for col, buf in sk["lag"].items():
            kk = buf.shape[0]
            jj = jnp.arange(kk, dtype=jnp.int32)
            prev = state["lag"][col][jnp.clip(jj - sk["count"], 0, kk - 1)]
            new["lag"][col] = jnp.where(combine & (jj >= sk["count"]), prev,
                                        buf)
        # an empty shard leaves the prefix state untouched
        state = jax.tree.map(
            lambda n, o: jnp.where(nonempty, n, o), new, state)
        states.append(state)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    return jax.tree.map(lambda x: x[k_of], stacked)


def _fold_window_lead_carry(gathered, by, p: int, k_of):
    """Right-to-left fold of the heading-group summaries (the lead
    counterpart of :func:`_fold_window_carry`): shard i's state describes
    the heading group of shards i+1..p-1."""
    def at(k):
        return jax.tree.map(lambda x: x[k], gathered)

    tuple_eq = A._tuple_eq

    s0 = at(0)
    state = {"has": jnp.asarray(False),
             "key": jax.tree.map(jnp.zeros_like, s0["first_by"]),
             "head_count": jnp.zeros((), jnp.int32),
             "head": jax.tree.map(jnp.zeros_like, s0["head"])}
    states = [None] * p
    for k in reversed(range(p)):
        states[k] = state
        if k == 0:
            break
        sk = at(k)
        nonempty = sk["rows"] > 0
        one_group = tuple_eq(sk["first_by"], sk["last_by"])
        cont = state["has"] & tuple_eq(sk["last_by"], state["key"])
        combine = nonempty & one_group & cont
        new = {
            "has": state["has"] | nonempty,
            "key": dict(sk["first_by"]),
            "head_count": jnp.where(combine,
                                    sk["rows"] + state["head_count"],
                                    sk["head_count"]),
            "head": {},
        }
        for col, buf in sk["head"].items():
            kk = buf.shape[0]
            jj = jnp.arange(kk, dtype=jnp.int32)
            nxt = state["head"][col][jnp.clip(jj - sk["rows"], 0, kk - 1)]
            new["head"][col] = jnp.where(combine & (jj >= sk["rows"]), nxt,
                                         buf)
        state = jax.tree.map(
            lambda n, o: jnp.where(nonempty, n, o), new, state)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    return jax.tree.map(lambda x: x[k_of], stacked)


def dist_window(
    table: Table,
    by: Sequence[str] | str,
    funcs,
    *,
    axis_name: str,
    bucket_capacity: int,
    order_by: Sequence[str] | str = (),
    samples_per_shard: int = 64,
    skip_shuffle: bool = False,
    use_kernel=None,
    report: list | None = None,
    stages: int | None = None,
    shuffle_mode: str = "alltoall",
):
    """Distributed window functions: range partition -> local sort ->
    per-shard segment scans + cross-shard boundary carry.

    The input is range-partitioned on (by + order_by) exactly like
    ``dist_sort`` (sampled lexicographic splitters), so after the local
    sort every shard holds a contiguous slice of the globally sorted
    frame. ``skip_shuffle`` is the provenance fast path: an input already
    range-partitioned on a (by + order_by) prefix — a ``dist_sort``
    output — skips both the AllToAll and pays only the boundary exchange.

    Groups that span shard boundaries are stitched EXACTLY: each shard
    publishes its trailing-group partial state (and heading-group lead
    values) in one p-sized ``all_gather`` of scalars/(K,) buffers — no
    AllToAll — and a static fold hands every shard the combined carry of
    all preceding (resp. following) shards. Bit-identical to the
    single-host ``ops_agg.window`` on integer-valued columns.
    """
    by_l = [by] if isinstance(by, str) else list(by)
    order_l = [order_by] if isinstance(order_by, str) else list(order_by)
    keys = by_l + order_l
    pairs = A.normalize_funcs(funcs)
    p = jax.lax.axis_size(axis_name)

    if skip_shuffle:
        t2, st = _shuffle(table, keys, axis_name=axis_name,
                          bucket_capacity=bucket_capacity, seed=0, skip=True,
                          report=report, label="window", stages=stages,
                          shuffle_mode=shuffle_mode)
    else:
        with jax.named_scope(EXCHANGE_SCOPE):
            pid = _lex_splitter_pids(table, keys, axis_name=axis_name,
                                     samples_per_shard=samples_per_shard)
        t2, st = _shuffle(table, keys, axis_name=axis_name,
                          bucket_capacity=bucket_capacity, seed=0, pid=pid,
                          report=report, label="window", stages=stages,
                          shuffle_mode=shuffle_mode)
    if t2.capacity == 0:
        t2 = Table({k: jnp.zeros((1,) + v.shape[1:], v.dtype)
                    for k, v in t2.columns.items()}, t2.row_count)
    A._window_validate(t2, by_l, order_l, pairs)
    sorted_t = L.sort_by(t2, keys)
    state = A.window_state(sorted_t, by_l, order_l)

    carry = lead_carry = None
    if p > 1:
        idx = jax.lax.axis_index(axis_name)
        summ = A.window_summary(sorted_t, state, by_l, order_l, pairs)
        gathered = jax.tree.map(
            lambda x: jax.lax.all_gather(x, axis_name), summ)
        carry = _fold_window_carry(gathered, by_l, order_l, p, idx)
        _, _, _, lead_req = A.carry_requirements(pairs)
        if lead_req:
            lsumm = A.window_lead_summary(sorted_t, state, by_l, pairs)
            lgathered = jax.tree.map(
                lambda x: jax.lax.all_gather(x, axis_name), lsumm)
            lead_carry = _fold_window_lead_carry(lgathered, by_l, p, idx)

    cols = A.window_sorted(sorted_t, state, by_l, order_l, pairs,
                           carry=carry, lead_carry=lead_carry,
                           use_kernel=use_kernel)
    out = Table({**sorted_t.columns, **cols}, sorted_t.row_count)
    return out, (st,)


def _lex_splitter_pids(table: Table, by: Sequence[str], *, axis_name: str,
                       samples_per_shard: int) -> jax.Array:
    """Sampled range partition over one or more key columns.

    Each key column maps through the order-preserving ``ordered_u32``
    transform; splitter *tuples* come from a global lexicographic sort of
    the per-shard samples. Row destinations generalize ``searchsorted(...,
    side='right')``: ``pid[r] = #{s : splitter_s <= row_r}`` under
    lexicographic order — computed against the (num_shards-1) splitters by
    a short comparison cascade, which sidesteps packing multi-key tuples
    into a single wide integer (no uint64 without x64 on this stack).
    """
    p = jax.lax.axis_size(axis_name)
    valid = table.valid_mask()
    c = table.capacity
    stride = max(1, c // samples_per_shard)

    row_keys, samples = [], []
    for k in by:
        ku = L.ordered_u32(table.columns[k])
        row_keys.append(ku)
        # stride-sample this shard's keys (max-sentinel where invalid, so
        # garbage rows sort to the tail of the global sample)
        samp = jnp.where(valid, ku, jnp.uint32(0xFFFFFFFF))
        samples.append(samp[::stride][:samples_per_shard])
    gathered = tuple(jax.lax.all_gather(s, axis_name).reshape(-1)
                     for s in samples)
    ordered = jax.lax.sort(gathered, num_keys=len(gathered))
    if not isinstance(ordered, (tuple, list)):
        ordered = (ordered,)
    # p-1 splitter tuples at even quantiles of the global sample
    n_s = ordered[0].shape[0]
    qs = (jnp.arange(1, p) * n_s) // p
    splitters = [col[qs] for col in ordered]  # each (p-1,)

    # lexicographic splitter <= row, per (splitter, row) pair
    pid = _lex_cascade_pid(splitters, row_keys, c, strict=False)
    return jnp.where(valid, pid, -1)


def dist_sort(
    table: Table,
    by: Sequence[str] | str,
    *,
    axis_name: str,
    bucket_capacity: int,
    samples_per_shard: int = 64,
    skip_shuffle: bool = False,
    report: list | None = None,
    stages: int | None = None,
    shuffle_mode: str = "alltoall",
):
    """Global sort: sampled range partition, then local sort per shard.

    ``by`` may name several key columns — splitters are then lexicographic
    tuples, so the global order is the multi-column lexicographic order.
    Output ordering: shard i holds keys <= shard i+1's keys; each shard is
    locally sorted — the standard distributed sort contract.
    """
    by_l = [by] if isinstance(by, str) else list(by)
    if skip_shuffle:  # single shard (or provably range-partitioned already)
        _, st = _shuffle(table, by_l, axis_name=axis_name,
                         bucket_capacity=bucket_capacity, seed=0, skip=True,
                         report=report, label="sort", stages=stages,
                         shuffle_mode=shuffle_mode)
        return L.sort_by(table, by_l), (st,)
    with jax.named_scope(EXCHANGE_SCOPE):
        pid = _lex_splitter_pids(table, by_l, axis_name=axis_name,
                                 samples_per_shard=samples_per_shard)
    out, st = _shuffle(table, by_l, axis_name=axis_name,
                       bucket_capacity=bucket_capacity, seed=0, pid=pid,
                       report=report, label="sort", stages=stages,
                       shuffle_mode=shuffle_mode)
    return L.sort_by(out, by_l), (st,)
