"""Local relational operators (Cylon §II-B) as pure, jittable JAX functions.

Every operator preserves the Table invariant (valid rows compacted to the
front, static capacity) and matches a NumPy oracle exactly — see
tests/test_relational_oracle.py (hypothesis property tests).

Cylon's operator set:   Select, Project, Join (inner/left/right/full-outer;
hash & sort algorithms), Union, Intersect, Difference (+ the local building
blocks Sort, Merge, HashPartition, Distinct).

TPU adaptation notes
--------------------
* Variable-size outputs become (capacity, row_count) with compaction — a
  stable argsort on validity, i.e. O(C log C) dense vector work instead of
  pointer chasing.
* The *sort* join sorts raw keys (exact). The *hash* join hashes the key
  columns with the Pallas murmur3 kernel and sorts 32-bit hashes —
  candidates are verified against the real keys, so collisions cost only
  capacity, never correctness (incl. outer joins, via the rescue segment).
* The join finds each left row's matches by merging its two sorted sides
  (two sorts and scans), and expands match counts into output slots by a
  sorted scatter-max and a running max, where the shapes favour it: no
  loop of bisection gathers, which are slow on the TPU. A few probe rows
  into many keep the scan search (``_pass_pays``).
* Set ops hash whole rows for partitioning but compare real columns for
  equality (lexicographic multi-operand lax.sort), so they are exact.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.table import Table, concat_tables
from repro.kernels import ops as kops

# ---------------------------------------------------------------------------
# compaction / select / project
# ---------------------------------------------------------------------------


def compact(table: Table, keep: jax.Array) -> Table:
    """Keep rows where `keep & valid`, compacted to the front (stable)."""
    with jax.named_scope("engine.step.compact"):
        keep = keep & table.valid_mask()
        order = jnp.argsort(~keep, stable=True)
        return table.gather(order, jnp.sum(keep), fill_invalid=False)


def select(table: Table, predicate: Callable[[dict], jax.Array]) -> Table:
    """Cylon Select: filter rows by a user predicate over the columns dict.

    Pleasingly parallel — no communication in the distributed version.
    """
    return compact(table, predicate(table.columns))


def project(table: Table, columns: Sequence[str]) -> Table:
    """Cylon Project: keep a subset of columns (row-count preserved)."""
    return Table({k: table.columns[k] for k in columns}, table.row_count)


def head(table: Table, n: int) -> Table:
    cols = {k: v[:n] for k, v in table.columns.items()}
    return Table(cols, jnp.minimum(table.row_count, n))


# ---------------------------------------------------------------------------
# sort / merge
# ---------------------------------------------------------------------------


def ordered_u32(x: jax.Array) -> jax.Array:
    """Order-preserving map to uint32 (for the bitonic kernel path)."""
    if x.dtype == jnp.uint32:
        return x
    if x.dtype == jnp.int32:
        return x.astype(jnp.uint32) ^ jnp.uint32(0x80000000)
    if x.dtype == jnp.float32:
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        flip = jnp.where(
            (u >> 31) == 1, jnp.uint32(0xFFFFFFFF), jnp.uint32(0x80000000)
        )
        return u ^ flip
    raise TypeError(f"unsupported sort key dtype {x.dtype}")


def sort_permutation(
    table: Table, by: Sequence[str], *, algorithm: str = "auto"
) -> jax.Array:
    """Permutation sorting valid rows ascending by `by`, invalid rows last.

    algorithm: 'auto' | 'xla' | 'bitonic'. The bitonic path (single key,
    capacity <= one VMEM tile) runs the Pallas comparator-network kernel;
    'auto' picks it when applicable.
    """
    c = table.capacity
    invalid = (~table.valid_mask()).astype(jnp.int32)
    keys = [table.columns[k] for k in by]
    use_bitonic = algorithm == "bitonic" or (
        algorithm == "auto" and len(keys) == 1 and c <= 2048
        and keys[0].dtype in (jnp.int32, jnp.uint32, jnp.float32)
    )
    if use_bitonic and len(keys) == 1:
        ku = ordered_u32(keys[0])
        # invalid rows -> max sentinel; the kernel's (key, iota) lexicographic
        # tie-break sorts them after valid max-key rows (front-compaction
        # guarantees invalid rows have larger original indices).
        ku = jnp.where(invalid == 1, jnp.uint32(0xFFFFFFFF), ku)
        _, perm = kops.sort_pairs(ku, jnp.arange(c, dtype=jnp.int32))
        return perm
    ops = (invalid, *keys, jnp.arange(c, dtype=jnp.int32))
    out = jax.lax.sort(ops, num_keys=1 + len(keys))
    return out[-1]


def sort_by(table: Table, by: Sequence[str] | str, *, algorithm: str = "auto") -> Table:
    by = [by] if isinstance(by, str) else list(by)
    perm = sort_permutation(table, by, algorithm=algorithm)
    with jax.named_scope("engine.step.permute"):
        return table.gather(perm, table.row_count, fill_invalid=False)


def merge(a: Table, b: Table, by: Sequence[str] | str) -> Table:
    """Merge two tables sorted by `by` into one sorted table.

    (Concat + sort; XLA's sort lowering on pre-sorted runs is the merge
    network — a dedicated 2-way bitonic merge pass is a kernel TODO.)
    """
    return sort_by(concat_tables(a, b), by)


# ---------------------------------------------------------------------------
# hash partition
# ---------------------------------------------------------------------------


def hash_partition(
    table: Table, key_columns: Sequence[str], num_partitions: int, *, seed: int = 0
):
    """Cylon HashPartition: per-row destination + per-bucket histogram.

    Returns (part_id (capacity,) int32 with -1 on invalid rows,
             histogram (num_partitions,) int32).
    """
    h = kops.hash_columns([table.columns[k] for k in key_columns], seed=seed)
    pid = (h % jnp.uint32(num_partitions)).astype(jnp.int32)
    pid = jnp.where(table.valid_mask(), pid, -1)
    hist = kops.bucket_histogram(pid, num_partitions)
    return pid, hist


# ---------------------------------------------------------------------------
# distinct & set operators (union / intersect / difference)
# ---------------------------------------------------------------------------


def _lex_sorted_with_tags(table: Table, tag: jax.Array):
    """Sort rows lexicographically over all columns (valid first)."""
    names = table.column_names
    invalid = (~table.valid_mask()).astype(jnp.int32)
    ops = (
        invalid,
        *[table.columns[k] for k in names],
        tag,
        jnp.arange(table.capacity, dtype=jnp.int32),
    )
    out = jax.lax.sort(ops, num_keys=1 + len(names) + 1)  # ... , tag as key
    sorted_cols = dict(zip(names, out[1 : 1 + len(names)]))
    return sorted_cols, out[-2], out[-1], out[0]  # cols, tags, perm, invalid


def _rows_equal(cols: dict, j_shift: int) -> jax.Array:
    """Row i equals row i+j_shift (element-wise over all columns; wraps)."""
    eq = None
    for v in cols.values():
        e = v == jnp.roll(v, -j_shift)
        eq = e if eq is None else (eq & e)
    return eq


def distinct(table: Table) -> Table:
    """Drop duplicate rows (whole-row equality), keep first occurrence."""
    zero_tag = jnp.zeros((table.capacity,), jnp.int32)
    cols, _, perm, invalid = _lex_sorted_with_tags(table, zero_tag)
    eq_prev = jnp.roll(_rows_equal(cols, 1), 1).at[0].set(False)
    valid = invalid == 0
    keep_sorted = valid & ~(eq_prev & jnp.roll(valid, 1))
    # map keep flags back to original order, then compact stably
    keep = jnp.zeros((table.capacity,), bool).at[perm].set(keep_sorted)
    return compact(table, keep)


def _set_op(a: Table, b: Table, keep_rule: str) -> Table:
    """Shared machinery: distinct each side, tag, lex-sort, neighbor tests."""
    assert a.schema == b.schema, "set ops need identical schemas"
    da, db = distinct(a), distinct(b)
    t = concat_tables(da, db)
    # concat_tables places b's valid rows right after a's valid rows.
    pos = jnp.arange(t.capacity)
    tag = ((pos >= da.row_count) & (pos < da.row_count + db.row_count)).astype(jnp.int32)
    cols, tags, perm, invalid = _lex_sorted_with_tags(t, tag)
    valid = invalid == 0
    eq_next = _rows_equal(cols, 1) & valid & jnp.roll(valid, -1)
    eq_next = eq_next.at[-1].set(False)
    eq_prev = jnp.roll(eq_next, 1).at[0].set(False)
    # after per-side distinct, an equal-run has length <= 2 (one per side),
    # with the tag-0 (a) row first because tag is a sort key.
    if keep_rule == "intersect":
        keep_sorted = valid & (tags == 0) & eq_next
    elif keep_rule == "difference_symmetric":
        keep_sorted = valid & ~eq_next & ~eq_prev
    elif keep_rule == "difference_left":
        keep_sorted = valid & (tags == 0) & ~eq_next
    else:
        raise ValueError(keep_rule)
    keep = jnp.zeros((t.capacity,), bool).at[perm].set(keep_sorted)
    return compact(t, keep)


def union(a: Table, b: Table) -> Table:
    """Cylon Union: all rows from both tables, duplicates removed."""
    assert a.schema == b.schema, "union needs identical schemas"
    return distinct(concat_tables(a, b))


def intersect(a: Table, b: Table) -> Table:
    """Cylon Intersect: rows present in both tables (set semantics)."""
    return _set_op(a, b, "intersect")


def difference(a: Table, b: Table, *, mode: str = "symmetric") -> Table:
    """Cylon Difference (paper Table I: symmetric). mode='left' for SQL EXCEPT."""
    return _set_op(a, b, f"difference_{mode}")


# ---------------------------------------------------------------------------
# join
# ---------------------------------------------------------------------------


def _sorted_keys(table: Table, key: jax.Array):
    """(sorted key w/ max-sentinel on invalid rows, permutation)."""
    sentinel = kops.key_max(key.dtype)
    k = jnp.where(table.valid_mask(), key, sentinel)
    perm = jnp.argsort(k, stable=True)  # invalid rows are last (stable + front-compaction)
    return k[perm], perm


@jax.jit
def merge_search(a: jax.Array, v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(searchsorted(a, v, side="left"), searchsorted(a, v, side="right"))``
    for a sorted ``a``, by one merge of the two vectors: sort and scan, no
    gather loop.

    ``a`` and ``v`` sort together by (key, position), so ``a``'s rows come
    before ``v``'s on equal keys. The running count of ``a``'s rows read at
    a ``v`` row is its right side; that count at the start of the row's run
    of equal keys is its left side. Runs use the sort's own equality, under
    which NaN equals NaN and -0.0 equals +0.0, as ``jnp.searchsorted``'s
    comparisons do. A second sort by position brings ``v``'s rows back to
    their order.
    """
    n = a.shape[0]
    keys = jnp.concatenate([a, v])
    pos = jnp.arange(keys.shape[0], dtype=jnp.int32)
    keys, pos = jax.lax.sort((keys, pos), num_keys=2, is_stable=False)
    from_a = (pos < n).astype(jnp.int32)
    le = jnp.cumsum(from_a)
    same = keys[1:] == keys[:-1]
    if jnp.issubdtype(keys.dtype, jnp.floating):
        same |= jnp.isnan(keys[1:]) & jnp.isnan(keys[:-1])
    run_start = jnp.concatenate([jnp.ones((1,), bool), ~same])
    lt = jax.lax.cummax(jnp.where(run_start, le - from_a, 0))
    _, lt, le = jax.lax.sort((pos, lt, le), num_keys=1, is_stable=False)
    return lt[n:], le[n:]


@functools.partial(jax.jit, static_argnums=1)
def expand_slots(off: jax.Array, slots: int) -> jax.Array:
    """``searchsorted(off, arange(slots), side="right") - 1`` for
    nondecreasing offsets ``off >= 0``: the row each of ``slots`` output
    slots expands, by one sorted scatter-max of each row at its offset and
    a running max, no gather loop."""
    rows = jnp.arange(off.shape[0], dtype=jnp.int32)
    mark = jnp.full((slots,), -1, jnp.int32).at[off].max(
        rows, mode="drop", indices_are_sorted=True)
    return jax.lax.cummax(mark)


#: What one row of a sorting pass (``merge_search``, ``expand_slots``)
#: costs, in gathers of one bisection step. A scan search of m queries
#: into n sorted rows gathers m rows in each of ceil(log2(n + 1)) steps;
#: a pass touches the n + m rows a few times. TPU v5e, one chip, int32
#: (measured): a bisection step gathers 25.2-27.5 ns per query into 30M
#: rows, 7.2 ns into 1M; merge_search costs 7.5 ns per row at 60M rows,
#: 3.4 ns at 2M; expand_slots 5.2 ns per row at 52.5M. So a pass row costs
#: 0.2-0.3 of a gather at the sizes where the searches take seconds, 0.5 at
#: a few million rows, where either way takes milliseconds.
_PASS_ROW_GATHERS = 0.3


def _pass_pays(n: int, m: int, searches: int) -> bool:
    """Whether one pass over ``n`` sorted rows and ``m`` queries costs less
    than ``searches`` scan searches of the queries (shapes are static): a
    few queries into many rows keep the scan."""
    steps = math.ceil(math.log2(n + 1))
    return (n + m) * _PASS_ROW_GATHERS < searches * m * steps


def search_bounds(a: jax.Array, v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``searchsorted(a, v)`` on the left and on the right side of each
    query: one ``merge_search`` where the shapes favour it, else two scan
    searches."""
    if _pass_pays(a.shape[0], v.shape[0], 2):
        return merge_search(a, v)
    return (jnp.searchsorted(a, v, side="left"),
            jnp.searchsorted(a, v, side="right"))


def slot_rows(off: jax.Array, slots: int) -> jax.Array:
    """``searchsorted(off, arange(slots), side="right") - 1``: by
    ``expand_slots`` where the shapes favour it, else by a scan search."""
    if _pass_pays(off.shape[0], slots, 1):
        return expand_slots(off, slots)
    return jnp.searchsorted(off, jnp.arange(slots), side="right") - 1


def join(
    left: Table,
    right: Table,
    on: Sequence[str] | str,
    *,
    how: str = "inner",
    algorithm: str = "sort",
    out_capacity: int | None = None,
    suffix: str = "_r",
    seed: int = 0,
    with_overflow: bool = False,
    _hash_fn=None,
) -> Table:
    """Cylon Join — all four semantics, both paper algorithms.

    algorithm='sort': exact sort-merge on the raw key (single numeric key).
    algorithm='hash': murmur3 hash of the key column(s) (Pallas kernel),
      sort/search on 32-bit hashes, verify candidates on real keys.
      Required for multi-column keys.

    Output columns: all left columns + right columns (clashes suffixed).
    Unmatched side fills with 0 (static-shape NULL analog; see DESIGN.md).

    ``with_overflow``: also return an int32 scalar counting result rows
    the ``out_capacity`` budget truncated (0 = exact). The cost model
    sizes out_capacity from cardinality estimates; this counter is what
    makes an underestimate loud (it feeds the distributed overflow-retry
    path) instead of a silently short result.
    """
    on = [on] if isinstance(on, str) else list(on)
    assert how in ("inner", "left", "right", "full"), how

    def _min_cap1(t: Table) -> Table:
        if t.capacity > 0:
            return t
        return Table({k: jnp.zeros((1,) + v.shape[1:], v.dtype)
                      for k, v in t.columns.items()}, t.row_count)

    left, right = _min_cap1(left), _min_cap1(right)
    c_l, c_r = left.capacity, right.capacity
    if out_capacity is None:
        out_capacity = c_l + c_r

    if algorithm == "sort":
        assert len(on) == 1, "sort join supports a single key column (use hash)"
        key_l, key_r = left.columns[on[0]], right.columns[on[0]]
        assert key_l.dtype == key_r.dtype, (key_l.dtype, key_r.dtype)
        verify = False
    elif algorithm == "hash":
        hf = _hash_fn or (lambda cols: kops.hash_columns(cols, seed=seed))
        key_l = hf([left.columns[k] for k in on])
        key_r = hf([right.columns[k] for k in on])
        verify = True
    else:
        raise ValueError(algorithm)

    lk, lperm = _sorted_keys(left, key_l)
    rk, rperm = _sorted_keys(right, key_r)
    n_l, n_r = left.row_count, right.row_count

    # the step scopes name the join's row searches and output gathers in
    # the compiled program's metadata (its operator scope is the plan's)
    with jax.named_scope("engine.step.search"):
        start, end = search_bounds(rk, lk)
        start, end = jnp.minimum(start, n_r), jnp.minimum(end, n_r)
    l_valid = jnp.arange(c_l) < n_l
    counts = jnp.where(l_valid, end - start, 0)

    # --- primary segment: candidate pair expansion (slot -> (li, ri)) -----
    off = jnp.cumsum(counts) - counts
    total = jnp.sum(counts)
    t = jnp.arange(out_capacity)
    with jax.named_scope("engine.step.search"):
        li = jnp.clip(slot_rows(off, out_capacity), 0, c_l - 1)
    with jax.named_scope("engine.step.emit"):
        j = t - off[li]
        ri = jnp.clip(start[li] + j, 0, c_r - 1)
        slot_valid = t < total

        l_orig = lperm[li]
        r_orig = rperm[ri]

        if verify:
            eq = jnp.ones((out_capacity,), bool)
            for k in on:
                eq &= left.columns[k][l_orig] == right.columns[k][r_orig]
            slot_valid &= eq

    def out_table(l_idx, r_idx, n):
        def take(col, idx, cap):
            with jax.named_scope("engine.step.emit"):
                v = col[jnp.clip(idx, 0, cap - 1)]
                sel = idx.reshape(idx.shape + (1,) * (col.ndim - 1)) >= 0
                return jnp.where(sel, v, jnp.zeros_like(v))

        cols = {}
        for k in left.column_names:
            cols[k] = take(left.columns[k], l_idx, c_l)
        for k in right.column_names:
            name = k + suffix if k in left.columns else k
            cols[name] = take(right.columns[k], r_idx, c_r)
        return Table(cols, jnp.asarray(n, jnp.int32))

    primary = compact(
        out_table(jnp.where(slot_valid, l_orig, -1), jnp.where(slot_valid, r_orig, -1),
                  out_capacity),
        slot_valid,
    )
    segments = [primary]
    # rows the result WOULD hold with unbounded capacity: the true match
    # count (`total` is computed before slot enumeration; under the hash
    # algorithm it includes collision candidates — a conservative over-
    # count) plus any unmatched-side rows accumulated below
    want_rows = total.astype(jnp.int32)

    if how in ("left", "full"):
        # true-match count per (sorted) left row; rows with none emit unmatched
        true_cnt = jnp.zeros((c_l,), jnp.int32).at[li].add(
            slot_valid.astype(jnp.int32), mode="drop"
        )
        l_unmatched = l_valid & (true_cnt == 0)
        want_rows = want_rows + jnp.sum(l_unmatched.astype(jnp.int32))
        seg = compact(
            out_table(jnp.where(l_unmatched, lperm, -1),
                      jnp.full((c_l,), -1, jnp.int32), c_l),
            l_unmatched,
        )
        segments.append(seg)

    if how in ("right", "full"):
        matched_r = jnp.zeros((c_r,), jnp.int32).at[
            jnp.where(slot_valid, ri, c_r)
        ].add(1, mode="drop")
        r_valid = jnp.arange(c_r) < n_r
        r_unmatched = r_valid & (matched_r == 0)
        want_rows = want_rows + jnp.sum(r_unmatched.astype(jnp.int32))
        seg = compact(
            out_table(jnp.full((c_r,), -1, jnp.int32),
                      jnp.where(r_unmatched, rperm, -1), c_r),
            r_unmatched,
        )
        segments.append(seg)

    result = segments[0]
    for seg in segments[1:]:
        result = concat_tables(result, seg)
    # trim back to the requested capacity (valid rows are front-compacted)
    if result.capacity > out_capacity:
        result = Table(
            {k: v[:out_capacity] for k, v in result.columns.items()},
            jnp.minimum(result.row_count, out_capacity),
        )
    if with_overflow:
        overflow = jnp.maximum(want_rows - out_capacity, 0).astype(jnp.int32)
        return result, overflow
    return result
