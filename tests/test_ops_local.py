"""Property tests: every local relational operator vs the NumPy oracle
(Cylon Table I semantics — select/project/join x4 x2 algos/union/
intersect/difference/sort/distinct)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ops_local as L
from repro.core.table import Table

from oracle import (
    difference_oracle, distinct_oracle, intersect_oracle, join_oracle,
    select_oracle, table_rows_sorted, union_oracle)

keys = st.integers(0, 8)  # small key range -> many duplicates/matches


@st.composite
def kv_table(draw, max_rows=14):
    n = draw(st.integers(0, max_rows))
    return {
        "k": np.asarray(draw(st.lists(keys, min_size=n, max_size=n)), np.int32),
        "v": np.asarray(draw(st.lists(st.integers(-50, 50), min_size=n,
                                      max_size=n)), np.int32),
    }


def as_table(cols, pad=3):
    return Table.from_arrays(cols, capacity=len(cols["k"]) + pad)


# --- select / project -------------------------------------------------------


@given(kv_table(), st.integers(0, 8))
def test_select(cols, thresh):
    t = as_table(cols)
    out = L.select(t, lambda c: c["k"] < thresh)
    assert table_rows_sorted(out) == \
        select_oracle(cols, lambda r: r["k"] < thresh)


@given(kv_table())
def test_project(cols):
    t = as_table(cols)
    out = L.project(t, ["k"])
    assert out.column_names == ["k"]
    assert sorted(out.to_numpy()["k"].tolist()) == sorted(cols["k"].tolist())


# --- sort / distinct ---------------------------------------------------------


@given(kv_table())
def test_sort_by(cols):
    t = as_table(cols)
    out = L.sort_by(t, "k")
    got = out.to_numpy()["k"]
    np.testing.assert_array_equal(got, np.sort(cols["k"], kind="stable"))


@given(kv_table())
def test_sort_bitonic_matches_xla(cols):
    t = as_table(cols)
    a = L.sort_by(t, "k", algorithm="bitonic").to_numpy()["k"]
    b = L.sort_by(t, "k", algorithm="xla").to_numpy()["k"]
    np.testing.assert_array_equal(a, b)


@given(kv_table())
def test_distinct(cols):
    t = as_table(cols)
    assert table_rows_sorted(L.distinct(t)) == distinct_oracle(cols)


# --- set operators -----------------------------------------------------------


@given(kv_table(), kv_table())
def test_union(a, b):
    assert table_rows_sorted(L.union(as_table(a), as_table(b))) == \
        union_oracle(a, b)


@given(kv_table(), kv_table())
def test_intersect(a, b):
    assert table_rows_sorted(L.intersect(as_table(a), as_table(b))) == \
        intersect_oracle(a, b)


@given(kv_table(), kv_table())
def test_difference_symmetric(a, b):
    assert table_rows_sorted(L.difference(as_table(a), as_table(b))) == \
        difference_oracle(a, b, "symmetric")


@given(kv_table(), kv_table())
def test_difference_left(a, b):
    assert table_rows_sorted(
        L.difference(as_table(a), as_table(b), mode="left")) == \
        difference_oracle(a, b, "left")


# --- join: 4 semantics x 2 algorithms ----------------------------------------


#: right-side padding that puts the join's start / end search on each path
#: of the shape rule: like-sized sides merge, a few probe rows into 2000
#: slots keep the scan search
SEARCH_PAD = {"merge": 2, "scan": 2000}


@pytest.mark.parametrize("search", sorted(SEARCH_PAD))
@pytest.mark.parametrize("how", ["inner", "left", "right", "full"])
@pytest.mark.parametrize("algorithm", ["sort", "hash"])
@settings(max_examples=20)
@given(left=kv_table(max_rows=10), right=kv_table(max_rows=10))
def test_join(search, how, algorithm, left, right):
    lt = as_table(left)
    rt = Table.from_arrays({"k": right["k"], "w": right["v"]},
                           capacity=len(right["k"]) + SEARCH_PAD[search])
    assert L._pass_pays(rt.capacity, lt.capacity, 2) == (search == "merge")
    out = L.join(lt, rt, "k", how=how, algorithm=algorithm,
                 out_capacity=(len(left["k"]) + 1) * (len(right["k"]) + 1)
                 + len(left["k"]) + len(right["k"]) + 2)
    _, expect = join_oracle(left, {"k": right["k"], "w": right["v"]},
                            ["k"], how=how)
    assert table_rows_sorted(out) == expect


# --- the join's row searches: merge or scan, both equal searchsorted ----------

SPECIAL = {
    np.int32: [0, 1, -1, 7, np.iinfo(np.int32).min, np.iinfo(np.int32).max],
    np.uint32: [0, 1, 7, 2**31, np.iinfo(np.uint32).max],
    np.float32: [0.0, -0.0, 1.5, -2.0, np.inf, -np.inf, np.nan, -np.nan,
                 np.finfo(np.float32).max],
}
#: (sorted rows n, queries m, path the shape rule takes for two searches)
SEARCH_SHAPES = [(1, 1, "merge"), (4, 60, "merge"), (60, 4, "merge"),
                 (16, 16, "merge"), (2000, 4, "scan"), (1, 64, "merge")]


def _keys(draw, dtype, size):
    """``size`` keys of ``dtype``, special values among them; a long
    vector is drawn from a seed, a short one value by value."""
    pool = SPECIAL[dtype] + list(range(6))
    if size > 64:
        rng = np.random.default_rng(draw(st.integers(0, 2**32 - 1)))
        return np.asarray(pool, dtype)[rng.integers(0, len(pool), size)]
    vals = st.sampled_from(pool)
    return np.asarray(draw(st.lists(vals, min_size=size, max_size=size))
                      ).astype(dtype)


@pytest.mark.parametrize("n,m,path", SEARCH_SHAPES)
@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.float32],
                         ids=["int32", "uint32", "float32"])
@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_search_bounds_match_searchsorted(dtype, n, m, path, data):
    """Duplicates, runs of the max sentinel, -0.0 / +0.0, NaN and inf:
    the merge and the scan give searchsorted's integers on both sides."""
    a = jnp.sort(jnp.asarray(_keys(data.draw, dtype, n)))
    v = jnp.sort(jnp.asarray(_keys(data.draw, dtype, m)))
    want = [jnp.searchsorted(a, v, side=s) for s in ("left", "right")]
    assert L._pass_pays(n, m, 2) == (path == "merge")
    for got in (L.search_bounds(a, v), L.merge_search(a, v)):
        for w, g in zip(want, got):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


#: (rows m, output slots, path the shape rule takes for one search)
EXPAND_SHAPES = [(1, 1, "scatter"), (12, 40, "scatter"), (40, 3, "scatter"),
                 (200, 4, "scan"), (3, 200, "scatter")]


@pytest.mark.parametrize("m,slots,path", EXPAND_SHAPES)
@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_slot_expansion_matches_searchsorted(m, slots, path, data):
    """The join's slot -> row expansion of the offsets of per-row match
    counts (zero-count rows included) equals searchsorted(off, t) - 1."""
    counts = np.asarray(data.draw(st.lists(
        st.integers(0, 3) | st.just(0), min_size=m, max_size=m)), np.int32)
    off = jnp.asarray(np.cumsum(counts) - counts)
    want = jnp.searchsorted(off, jnp.arange(slots), side="right") - 1
    assert L._pass_pays(m, slots, 1) == (path == "scatter")
    for got in (L.slot_rows(off, slots), L.expand_slots(off, slots)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(left=kv_table(max_rows=10), right=kv_table(max_rows=10))
def test_join_multikey_hash(left, right):
    """Multi-column join (hash algorithm only, as in Cylon)."""
    lt = as_table(left)
    rt = Table.from_arrays({"k": right["k"], "v": right["v"]},
                           capacity=len(right["k"]) + 2)
    out = L.join(lt, rt, ["k", "v"], how="inner", algorithm="hash",
                 out_capacity=(len(left["k"]) + 1) * (len(right["k"]) + 1))
    _, expect = join_oracle(left, right, ["k", "v"], how="inner")
    assert table_rows_sorted(out) == expect


def test_join_overflow_truncates_to_capacity():
    """out_capacity smaller than the true result: valid rows kept, count
    clamped (Cylon's explicit memory-budget failure mode)."""
    a = Table.from_arrays({"k": np.zeros(4, np.int32)})
    b = Table.from_arrays({"k": np.zeros(4, np.int32), "w": np.arange(4, dtype=np.int32)})
    out = L.join(a, b, "k", out_capacity=5)
    assert int(out.row_count) == 5
    assert out.capacity == 5


@given(kv_table())
def test_head(cols):
    t = as_table(cols)
    h = L.head(t, 3)
    assert int(h.row_count) == min(3, len(cols["k"]))
