"""Keyed aggregation (GroupBy) — the paper's missing operator family.

Cylon's follow-up ("A Fast, Scalable, Universal Approach For Distributed
Data Aggregations", arXiv:2010.14596) treats keyed aggregation as the
workhorse of distributed data engineering. The local algorithm here is the
sort-based path adapted to the compacted-front Table invariant:

    sort keys  ->  segment-boundary detection  ->  segment reductions

The sort carries the keys and a row index, never the payload: each row's
group id is carried back to where the row lies, and the reductions read
the payload columns in place.

Exact multi-column keys throughout (the sort compares real key columns, as
in ops_local's sort path); hashing appears only as the distributed
pre-partitioner (ops_dist.dist_groupby). The segment reductions run on the
Pallas one-hot kernel (kernels/segment_reduce.py) for the hot 1-D shapes
and on XLA otherwise — the same semantics, with float sums accurate on
both (``kops.segment_reduce``), each under the ``engine.step.reduce``
scope.

Aggregators: sum / count / min / max / mean / var / first. Every aggregator
decomposes into *algebraic* partials (sum, sumsq, count, min, max, first)
that combine associatively across shards — the paper's two-phase
(partial-aggregate -> AllToAll -> final-combine) strategy falls out of the
same machinery: ``groupby == finalize ∘ partial_groupby`` locally, and
``finalize ∘ combine ∘ shuffle ∘ partial`` distributed.

Output Table: one row per group (compacted to the front, ordered by key),
columns = key columns + ``{col}_{agg}`` result columns.

Window functions (:func:`window`) ride the same sorted-segment machinery
but are ROW-preserving: sort by (keys, order), detect group segments and
value runs, then express every function as a segmented prefix scan
(``kernels/segment_scan.py``) or an in-segment gather. The module exposes
the building blocks (:func:`window_state`, :func:`window_sorted`,
:func:`window_summary`, :func:`window_lead_summary`) separately so
``ops_dist.dist_window`` can run them per shard over a globally sorted
frame and stitch shard boundaries with carried partial state instead of a
shuffle.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import ops_local as L
from repro.core.table import Table
from repro.kernels import ops as kops

AGG_OPS = ("sum", "count", "min", "max", "mean", "var", "first")

# aggregator -> algebraic partials it needs (combine: sums add, min/max
# re-reduce, first takes the earliest partial in global row order)
_DECOMP = {
    "sum": ("sum",),
    "count": ("count",),
    "min": ("min",),
    "max": ("max",),
    "mean": ("sum", "count"),
    "var": ("sum", "sumsq", "count"),
    "first": ("first",),
}
_COMBINE = {"sum": "sum", "sumsq": "sum", "count": "sum",
            "min": "min", "max": "max", "first": "first"}


def normalize_aggs(aggs) -> tuple[tuple[str, str], ...]:
    """Accept {col: op | [ops]} or [(col, op), ...] -> ((col, op), ...)."""
    if isinstance(aggs, dict):
        pairs = []
        for col, ops in aggs.items():
            ops = [ops] if isinstance(ops, str) else list(ops)
            pairs += [(col, op) for op in ops]
    else:
        pairs = [(c, o) for c, o in aggs]
    for col, op in pairs:
        assert op in AGG_OPS, (op, AGG_OPS)
    return tuple(pairs)


def _prim_name(col: str, prim: str) -> str:
    """Internal partial-column name (count is group size, column-free)."""
    return "__count" if prim == "count" else f"__{prim}__{col}"


def _segments(table: Table, keys: Sequence[str]):
    """Group the rows by keys without moving them -> (table, seg_id (cap,)
    int32 in the table's own row order [-1 invalid], num_groups, starts
    (cap,) int32 row index of each group's first row).

    One stable sort of (invalid, keys, row index) numbers the groups in key
    order; a second sort, by that row index, carries each row's group id
    back to the row. The payload columns are never reordered: the
    segment reductions take rows in any order."""
    if table.capacity == 0:
        table = Table({k: jnp.zeros((1,) + v.shape[1:], v.dtype)
                       for k, v in table.columns.items()}, table.row_count)
    cap = table.capacity
    rows = jnp.arange(cap, dtype=jnp.int32)
    invalid = (~table.valid_mask()).astype(jnp.int32)
    out = jax.lax.sort((invalid, *(table.columns[k] for k in keys), rows),
                       num_keys=1 + len(keys), is_stable=True)
    valid, skeys, perm = out[0] == 0, out[1:-1], out[-1]
    differs = jnp.zeros((cap,), bool)
    for col in skeys:
        differs = differs | (col != jnp.roll(col, 1))
    boundary = valid & (differs | (rows == 0))
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    seg = jnp.where(valid, seg, -1)
    num_groups = jnp.sum(boundary).astype(jnp.int32)
    # one boundary row per group: scatter its row index to slot seg[i]
    starts = jnp.zeros((cap,), jnp.int32).at[
        jnp.where(boundary, seg, cap)].set(perm, mode="drop")
    with jax.named_scope("engine.step.permute"):
        _, seg = jax.lax.sort((perm, seg), num_keys=1)
    return table, seg, num_groups, starts


def _first(col: jax.Array, starts: jax.Array, group_valid: jax.Array):
    """Per-group value at the segment start (stable sort => first in input
    order). Works for N-D payload columns."""
    v = col[starts]
    sel = group_valid.reshape((-1,) + (1,) * (col.ndim - 1))
    return jnp.where(sel, v, jnp.zeros_like(v))


def _reduce(col: jax.Array, seg: jax.Array, slots: int, prim: str,
            group_valid: jax.Array, use_kernel):
    """One algebraic partial over a (cap, ...) column -> (slots, ...)."""
    with jax.named_scope("engine.step.reduce"):
        if prim == "sumsq":
            col = col.astype(jnp.float32) ** 2
            prim = "sum"
        out = kops.segment_reduce(col, seg, slots, prim,
                                  use_kernel=use_kernel)
    # empty slots hold the op identity (e.g. +inf for min): zero them so
    # rows past row_count stay benign garbage
    sel = group_valid.reshape((-1,) + (1,) * (out.ndim - 1))
    return jnp.where(sel, out, jnp.zeros_like(out))


def _truncated(num_groups: jax.Array, slots: int) -> jax.Array:
    """int32 count of groups past the ``slots`` a groupby reduced into."""
    return jnp.maximum(num_groups - slots, 0).astype(jnp.int32)


def _partial_columns(table: Table, keys: Sequence[str], pairs, *,
                     out_capacity: int | None = None, use_kernel=None):
    """Shared phase-1 machinery: per-group key values + algebraic partials,
    and the count of groups that did not fit (:func:`_truncated`).

    Reductions run into ``out_capacity`` slots when given (groups past it
    truncate, mirroring join's explicit memory-budget failure mode) — a
    tight bound both shrinks the output table and keeps the segment count
    within the Pallas kernel's VMEM budget on large inputs.
    """
    st, seg, num_groups, starts = _segments(table, keys)
    cap = st.capacity
    slots = cap if out_capacity is None else min(cap, out_capacity)
    row_count = jnp.minimum(num_groups, slots)
    group_valid = jnp.arange(slots) < row_count
    starts = starts[:slots]

    cols: dict[str, jax.Array] = {}
    for k in keys:
        cols[k] = _first(st.columns[k], starts, group_valid)
    prims = {(c, p) for c, op in pairs for p in _DECOMP[op]}
    for col, prim in sorted(prims, key=lambda cp: _prim_name(*cp)):
        name = _prim_name(col, prim)
        if name in cols:
            continue  # shared count slot
        if prim == "count":
            ones = jnp.where(seg >= 0, 1, 0).astype(jnp.int32)
            cols[name] = _reduce(ones, seg, slots, "sum", group_valid,
                                 use_kernel)
        elif prim == "first":
            cols[name] = _first(st.columns[col], starts, group_valid)
        else:
            cols[name] = _reduce(st.columns[col], seg, slots, prim,
                                 group_valid, use_kernel)
    return Table(cols, row_count), _truncated(num_groups, slots)


def _finalize(partial: Table, keys: Sequence[str], pairs) -> Table:
    """Turn algebraic partials into the user-facing aggregate columns."""
    cols = {k: partial.columns[k] for k in keys}
    get = lambda c, p: partial.columns[_prim_name(c, p)]
    for col, op in pairs:
        name = f"{col}_{op}"
        if op in ("sum", "min", "max", "first"):
            cols[name] = get(col, op)
        elif op == "count":
            cols[name] = get(col, "count")
        elif op == "mean":
            s = get(col, "sum").astype(jnp.float32)
            n = jnp.maximum(get(col, "count"), 1).astype(jnp.float32)
            cols[name] = s / n.reshape((-1,) + (1,) * (s.ndim - 1))
        elif op == "var":  # population variance: E[x^2] - E[x]^2, clamped
            s = get(col, "sum").astype(jnp.float32)
            n = jnp.maximum(get(col, "count"), 1).astype(jnp.float32)
            n = n.reshape((-1,) + (1,) * (s.ndim - 1))
            mean = s / n
            cols[name] = jnp.maximum(get(col, "sumsq") / n - mean * mean, 0.0)
    return Table(cols, partial.row_count)


def groupby(table: Table, keys: Sequence[str] | str, aggs, *,
            out_capacity: int | None = None, use_kernel=None,
            with_overflow: bool = False):
    """Local GroupBy: one output row per distinct key tuple, ordered by key.

    keys: 1-D key column name(s) (exact multi-column comparison).
    aggs: {col: op | [ops]} or [(col, op), ...]; ops in AGG_OPS. N-D payload
    columns support sum/min/max/mean/first (element-wise per row-vector).
    Output columns: keys + ``{col}_{op}``; row_count = number of groups.
    ``with_overflow``: also return an int32 scalar counting the groups the
    ``out_capacity`` budget truncated (0 = exact), as ``ops_local.join``
    does for its rows.
    """
    keys = [keys] if isinstance(keys, str) else list(keys)
    pairs = normalize_aggs(aggs)
    partial, trunc = _partial_columns(table, keys, pairs,
                                      out_capacity=out_capacity,
                                      use_kernel=use_kernel)
    out = _finalize(partial, keys, pairs)
    return (out, trunc) if with_overflow else out


def partial_groupby(table: Table, keys: Sequence[str] | str, aggs, *,
                    out_capacity: int | None = None, use_kernel=None,
                    with_overflow: bool = False):
    """Phase 1 of the two-phase strategy: per-shard algebraic partials.

    Output rows are one per locally-distinct key (<= key cardinality, the
    shuffle-volume win); columns are the mangled partial slots + keys.
    ``with_overflow`` as in :func:`groupby`.
    """
    keys = [keys] if isinstance(keys, str) else list(keys)
    pairs = normalize_aggs(aggs)
    out, trunc = _partial_columns(table, keys, pairs,
                                  out_capacity=out_capacity,
                                  use_kernel=use_kernel)
    return (out, trunc) if with_overflow else out


def combine_groupby(partials: Table, keys: Sequence[str] | str, aggs, *,
                    out_capacity: int | None = None, use_kernel=None,
                    with_overflow: bool = False):
    """Phase 2: merge partial rows that share a key, then finalize.

    ``combine_groupby(partial_groupby(t, ...), ...) == groupby(t, ...)`` —
    and partials arriving from different shards (via repartition) combine
    the same way: sums add, min/max re-reduce, first takes the earliest
    partial in row order (repartition preserves source-shard order).
    ``with_overflow`` as in :func:`groupby`.
    """
    keys = [keys] if isinstance(keys, str) else list(keys)
    pairs = normalize_aggs(aggs)
    st, seg, num_groups, starts = _segments(partials, keys)
    cap = st.capacity
    slots = cap if out_capacity is None else min(cap, out_capacity)
    row_count = jnp.minimum(num_groups, slots)
    group_valid = jnp.arange(slots) < row_count
    starts = starts[:slots]

    cols = {k: _first(st.columns[k], starts, group_valid) for k in keys}
    for name in st.column_names:
        if not name.startswith("__"):
            continue
        prim = "count" if name == "__count" else name[2:].split("__", 1)[0]
        comb = _COMBINE[prim]
        if comb == "first":
            cols[name] = _first(st.columns[name], starts, group_valid)
        else:
            cols[name] = _reduce(st.columns[name], seg, slots, comb,
                                 group_valid, use_kernel)
    out = _finalize(Table(cols, row_count), keys, pairs)
    return (out, _truncated(num_groups, slots)) if with_overflow else out


# ---------------------------------------------------------------------------
# window functions (row-preserving analytics over sorted segments)
# ---------------------------------------------------------------------------

WINDOW_FUNCS = ("rank", "dense_rank", "row_number", "lag", "lead",
                "cumsum", "cummax", "running_mean")
_NO_COL_FUNCS = ("rank", "dense_rank", "row_number")
_SCAN_COL_FUNCS = ("cumsum", "cummax", "running_mean")


def normalize_funcs(funcs) -> tuple[tuple[str, str | None, int], ...]:
    """Canonicalize a window-function spec to ``((fn, col, offset), ...)``.

    Accepts a single string, or a sequence of: ``"rank"`` (column-free
    funcs), ``("cumsum", "d0")``, ``("lag", "d0")`` (offset defaults to
    1), ``("lag", "d0", 3)``. The canonical tuple is hashable — it is the
    plan-node field and part of the jit-cache key.
    """
    if isinstance(funcs, str):
        funcs = [funcs]
    out = []
    for f in funcs:
        if isinstance(f, str):
            fn, col, off = f, None, 0
        else:
            f = tuple(f)
            fn, col = f[0], f[1]
            off = int(f[2]) if len(f) > 2 else 0
        assert fn in WINDOW_FUNCS, (fn, WINDOW_FUNCS)
        if fn in _NO_COL_FUNCS:
            assert col is None, f"{fn} takes no column (got {col!r})"
        else:
            assert col is not None, f"{fn} needs a column"
        if fn in ("lag", "lead"):
            off = 1 if off == 0 else off
            assert off >= 1, (fn, off)
        else:
            assert off == 0, f"{fn} takes no offset"
        out.append((fn, col, off))
    return tuple(out)


def window_output_name(fn: str, col: str | None, offset: int = 0) -> str:
    """Output column name: ``rank`` / ``{col}_cumsum`` / ``{col}_lag`` /
    ``{col}_lag{k}`` for offsets beyond the default 1."""
    if col is None:
        return fn
    if fn in ("lag", "lead") and offset > 1:
        return f"{col}_{fn}{offset}"
    return f"{col}_{fn}"


def carry_requirements(pairs):
    """Static description of the cross-shard carry a funcs set needs:
    ``(sums, maxs, lag, lead)`` where sums maps internal slot name ->
    (col, 'native'|'f32'), maxs is a column set, lag/lead map col -> the
    largest requested offset (the boundary-buffer depth)."""
    sums: dict[str, tuple[str, str]] = {}
    maxs: set[str] = set()
    lag: dict[str, int] = {}
    lead: dict[str, int] = {}
    for fn, col, off in pairs:
        if fn == "cumsum":
            sums[f"cumsum:{col}"] = (col, "native")
        elif fn == "running_mean":
            sums[f"rmean:{col}"] = (col, "f32")
        elif fn == "cummax":
            maxs.add(col)
        elif fn == "lag":
            lag[col] = max(lag.get(col, 0), off)
        elif fn == "lead":
            lead[col] = max(lead.get(col, 0), off)
    return sums, maxs, lag, lead


def _dtype_min(dtype):
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


def _tuple_eq(cols_a, cols_b) -> jax.Array:
    """Scalar equality of two same-keyed dicts of scalars (True if empty)."""
    eq = jnp.asarray(True)
    for k in cols_a:
        eq = eq & (cols_a[k] == cols_b[k])
    return eq


def window_state(st: Table, by: Sequence[str], order_by: Sequence[str]):
    """Segment/run geometry of an ALREADY (by + order_by)-sorted table.

    Returns a dict of per-row arrays: ``seg`` (group id, -1 invalid),
    ``starts`` (group start row, scatter-indexed by group id), ``pos``
    (0-based position within group), ``vb`` (True at the first row of
    each (by + order_by) value run), ``num_groups``, and ``end_excl``
    (one past the row's group's last row).
    """
    cap = st.capacity
    valid = st.valid_mask()
    pos0 = jnp.arange(cap) == 0
    differs_by = jnp.zeros((cap,), bool)
    for k in by:
        col = st.columns[k]
        differs_by = differs_by | (col != jnp.roll(col, 1))
    boundary = valid & (differs_by | pos0)
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    seg = jnp.where(valid, seg, -1)
    num_groups = jnp.sum(boundary).astype(jnp.int32)
    starts = jnp.zeros((cap,), jnp.int32).at[
        jnp.where(boundary, seg, cap)].set(jnp.arange(cap, dtype=jnp.int32),
                                           mode="drop")
    differs_run = differs_by
    for k in order_by:
        col = st.columns[k]
        differs_run = differs_run | (col != jnp.roll(col, 1))
    vb = valid & (differs_run | pos0)
    pos = jnp.arange(cap, dtype=jnp.int32) - starts[
        jnp.clip(seg, 0, cap - 1)]
    pos = jnp.where(valid, pos, 0)
    next_start = starts[jnp.clip(seg + 1, 0, cap - 1)]
    end_excl = jnp.where(seg + 1 < num_groups, next_start, st.row_count)
    end_excl = jnp.where(valid, end_excl, 0)
    return {"seg": seg, "starts": starts, "pos": pos, "vb": vb,
            "num_groups": num_groups, "end_excl": end_excl}


def window_sorted(st: Table, state, by: Sequence[str],
                  order_by: Sequence[str], pairs, *, carry=None,
                  lead_carry=None, use_kernel=None) -> dict[str, jax.Array]:
    """Window output columns over a (by + order_by)-sorted table.

    ``carry`` / ``lead_carry`` are the cross-shard boundary states built
    by ``ops_dist`` (None for a purely local frame): ``carry`` folds the
    preceding shards' trailing-group partials into this shard's LEADING
    group, ``lead_carry`` folds the following shards' heading-group
    values into this shard's TRAILING group (lead only). Every function
    is exact under both — the distributed result is bit-identical to the
    single-host computation on integer-valued columns.
    """
    cap = st.capacity
    valid = st.valid_mask()
    seg, pos, vb = state["seg"], state["pos"], state["vb"]
    end_excl, num_groups = state["end_excl"], state["num_groups"]
    arange = jnp.arange(cap, dtype=jnp.int32)
    sums_req, maxs_req, lag_req, lead_req = carry_requirements(pairs)
    fns = {fn for fn, _, _ in pairs}

    rn = pos + 1  # 1-based row number within group
    dr_local = rk = None
    if "dense_rank" in fns or "rank" in fns:
        dr_local = kops.segment_scan(vb.astype(jnp.int32), seg, "sum",
                                     use_kernel=use_kernel)
        dr = dr_local
    if "rank" in fns:
        rk = kops.segment_scan(jnp.where(vb, rn, 0).astype(jnp.int32), seg,
                               "max", use_kernel=use_kernel)
    cs = {}
    for name, (col, kind) in sums_req.items():
        v = st.columns[col]
        v = v.astype(jnp.float32) if kind == "f32" else v
        cs[name] = kops.segment_scan(v, seg, "sum", use_kernel=use_kernel)
    cm = {col: kops.segment_scan(st.columns[col], seg, "max",
                                 use_kernel=use_kernel) for col in maxs_req}
    lg = {}
    ld = {}
    for fn, col, off in pairs:
        if fn == "lag":
            v = st.columns[col][jnp.clip(arange - off, 0, cap - 1)]
            lg[(col, off)] = jnp.where(valid & (pos >= off), v,
                                       jnp.zeros_like(v))
        elif fn == "lead":
            v = st.columns[col][jnp.clip(arange + off, 0, cap - 1)]
            ld[(col, off)] = jnp.where(valid & (arange + off < end_excl), v,
                                       jnp.zeros_like(v))

    if carry is not None:
        first_by = {k: st.columns[k][0] for k in by}
        match = carry["has"] & (st.row_count > 0) \
            & _tuple_eq(first_by, carry["key"])
        m = (seg == 0) & match
        C = carry["count"]
        if "rank" in fns or "dense_rank" in fns:
            first_order = {k: st.columns[k][0] for k in order_by}
            cont = match & _tuple_eq(first_order, carry["last_order"])
        if "rank" in fns:
            # rows continuing the previous shards' trailing VALUE RUN take
            # the run's global rank (C - E + 1); other leading-group rows
            # shift by the carried row count
            run0 = m & (dr_local == 1)
            rk = jnp.where(run0 & cont, C - carry["run_eq"] + 1,
                           jnp.where(m, rk + C, rk))
        if "dense_rank" in fns:
            dr = jnp.where(m, dr + carry["runs"] - cont.astype(jnp.int32),
                           dr)
        rn = jnp.where(m, rn + C, rn)
        for name in cs:
            cs[name] = jnp.where(m, cs[name] + carry["sums"][name], cs[name])
        for col in cm:
            cm[col] = jnp.where(m, jnp.maximum(cm[col], carry["maxs"][col]),
                                cm[col])
        for (col, off), v in lg.items():
            buf = carry["lag"][col]  # (K,): buf[j] = j+1 rows before the cut
            j = off - 1 - pos
            take = m & (pos < off) & (j < C)
            lg[(col, off)] = jnp.where(
                take, buf[jnp.clip(j, 0, buf.shape[0] - 1)], v)

    if lead_carry is not None:
        idx_last = jnp.maximum(st.row_count - 1, 0)
        last_by = {k: st.columns[k][idx_last] for k in by}
        match_l = lead_carry["has"] & (st.row_count > 0) \
            & _tuple_eq(last_by, lead_carry["key"])
        in_last = valid & (seg == num_groups - 1)
        e = end_excl - 1 - arange  # rows after this one within its group
        H = lead_carry["head_count"]
        for (col, off), v in ld.items():
            buf = lead_carry["head"][col]  # (K,): buf[j] = j-th row after cut
            j = off - 1 - e
            take = in_last & match_l & (e < off) & (j < H)
            ld[(col, off)] = jnp.where(
                take, buf[jnp.clip(j, 0, buf.shape[0] - 1)], v)

    out: dict[str, jax.Array] = {}
    for fn, col, off in pairs:
        name = window_output_name(fn, col, off)
        if fn == "row_number":
            out[name] = jnp.where(valid, rn, 0).astype(jnp.int32)
        elif fn == "rank":
            out[name] = jnp.where(valid, rk, 0).astype(jnp.int32)
        elif fn == "dense_rank":
            out[name] = jnp.where(valid, dr, 0).astype(jnp.int32)
        elif fn == "cumsum":
            v = cs[f"cumsum:{col}"]
            out[name] = jnp.where(valid, v, jnp.zeros_like(v))
        elif fn == "cummax":
            v = cm[col]
            out[name] = jnp.where(valid, v, jnp.zeros_like(v))
        elif fn == "running_mean":
            v = cs[f"rmean:{col}"] / jnp.maximum(rn, 1).astype(jnp.float32)
            out[name] = jnp.where(valid, v, 0.0)
        elif fn == "lag":
            out[name] = lg[(col, off)]
        elif fn == "lead":
            out[name] = ld[(col, off)]
    return out


def window_summary(st: Table, state, by: Sequence[str],
                   order_by: Sequence[str], pairs):
    """This shard's TRAILING-group boundary state (for the next shards).

    All scalars / fixed (K,) buffers — the per-shard payload of the
    boundary ``all_gather``: the trailing group's row count, algebraic
    partials (sum/max per carried column), value-run count, trailing-run
    size, the boundary key/order tuples, and the last ``K`` values per
    lag column (K = largest requested offset).
    """
    cap = st.capacity
    rc = st.row_count
    valid = st.valid_mask()
    idx_last = jnp.maximum(rc - 1, 0)
    starts, vb = state["starts"], state["vb"]
    num_groups = state["num_groups"]
    gstart = starts[jnp.clip(num_groups - 1, 0, cap - 1)]
    count = (rc - gstart).astype(jnp.int32)
    tm = (jnp.arange(cap) >= gstart) & valid
    sums_req, maxs_req, lag_req, _ = carry_requirements(pairs)

    eq_last = jnp.ones((cap,), bool)
    for k in order_by:
        col = st.columns[k]
        eq_last = eq_last & (col == col[idx_last])
    summ = {
        "rows": rc,
        "first_by": {k: st.columns[k][0] for k in by},
        "last_by": {k: st.columns[k][idx_last] for k in by},
        "first_order": {k: st.columns[k][0] for k in order_by},
        "last_order": {k: st.columns[k][idx_last] for k in order_by},
        "count": count,
        "runs": jnp.sum(vb & tm).astype(jnp.int32),
        "run_eq": jnp.sum(tm & eq_last).astype(jnp.int32),
        "sums": {}, "maxs": {}, "lag": {},
    }
    for name, (col, kind) in sums_req.items():
        v = st.columns[col]
        v = v.astype(jnp.float32) if kind == "f32" else v
        summ["sums"][name] = jnp.sum(jnp.where(tm, v, jnp.zeros_like(v)))
    for col in maxs_req:
        v = st.columns[col]
        summ["maxs"][col] = jnp.max(jnp.where(tm, v, _dtype_min(v.dtype)))
    for col, k in lag_req.items():
        idxs = rc - 1 - jnp.arange(k, dtype=jnp.int32)
        ok = (idxs >= gstart) & (idxs >= 0)
        v = st.columns[col][jnp.clip(idxs, 0, cap - 1)]
        summ["lag"][col] = jnp.where(ok, v, jnp.zeros_like(v))
    return summ


def window_lead_summary(st: Table, state, by: Sequence[str], pairs):
    """This shard's HEADING-group boundary state (for the previous shards):
    the heading group's row count and its first ``K`` values per lead
    column."""
    cap = st.capacity
    rc = st.row_count
    starts, num_groups = state["starts"], state["num_groups"]
    head = jnp.where(num_groups > 1, starts[jnp.clip(1, 0, cap - 1)], rc)
    head = head.astype(jnp.int32)
    _, _, _, lead_req = carry_requirements(pairs)
    idx_last = jnp.maximum(rc - 1, 0)
    summ = {
        "rows": rc,
        "first_by": {k: st.columns[k][0] for k in by},
        "last_by": {k: st.columns[k][idx_last] for k in by},
        "head_count": head,
        "head": {},
    }
    for col, k in lead_req.items():
        idxs = jnp.arange(k, dtype=jnp.int32)
        v = st.columns[col][jnp.clip(idxs, 0, cap - 1)]
        summ["head"][col] = jnp.where(idxs < head, v, jnp.zeros_like(v))
    return summ


def _window_validate(table: Table, by, order_by, pairs):
    for k in list(by) + list(order_by):
        assert table.columns[k].ndim == 1, f"window key {k!r} must be 1-D"
    for fn, col, off in pairs:
        name = window_output_name(fn, col, off)
        assert name not in table.columns, (
            f"window output {name!r} collides with an input column")
        if col is None:
            continue
        v = table.columns[col]
        assert v.ndim == 1, f"window input {col!r} must be 1-D"
        if fn in _SCAN_COL_FUNCS:
            assert v.dtype in (jnp.float32, jnp.int32), (
                f"{fn} needs f32/i32 input; {col!r} is {v.dtype}")


def window(table: Table, by: Sequence[str] | str, funcs, *,
           order_by: Sequence[str] | str = (), use_kernel=None) -> Table:
    """Window functions over sorted segments — row-preserving analytics.

    ``by``: partition key column(s); ``order_by``: in-group ordering
    column(s); ``funcs``: see :func:`normalize_funcs`. Returns the input
    rows SORTED by (by, order_by) — the canonical frame order — with one
    appended column per requested function (:func:`window_output_name`):

    ``rank``/``dense_rank``/``row_number`` (int32, 1-based; ties on the
    full (by, order_by) tuple share rank), ``lag``/``lead`` (the value
    ``offset`` rows away within the group, 0 outside it — the
    static-shape NULL analog), ``cumsum``/``cummax`` (running aggregate
    in the column dtype), ``running_mean`` (f32).
    """
    by = [by] if isinstance(by, str) else list(by)
    order = [order_by] if isinstance(order_by, str) else list(order_by)
    pairs = normalize_funcs(funcs)
    _window_validate(table, by, order, pairs)
    if table.capacity == 0:
        table = Table({k: jnp.zeros((1,) + v.shape[1:], v.dtype)
                       for k, v in table.columns.items()}, table.row_count)
    st = L.sort_by(table, by + order)
    state = window_state(st, by, order)
    cols = window_sorted(st, state, by, order, pairs, use_kernel=use_kernel)
    return Table({**st.columns, **cols}, st.row_count)
