"""GroupBy vs the NumPy oracle + segment-reduce kernel sweeps.

Deliberately hypothesis-free: this module is part of the minimal-environment
tier-1 gate (conftest skips the property-test modules when hypothesis is
absent; the groupby coverage must survive that).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ops_agg as A
from repro.core.table import Table, concat_tables
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.segment_reduce import segment_reduce_tiles

from oracle import groupby_oracle

RNG = np.random.default_rng(7)

ALL_AGGS = [("v", op) for op in A.AGG_OPS]


def check_vs_oracle(out: Table, table_dict, keys, aggs, atol=1e-4):
    """out rows (sorted by key, front-compacted) == oracle, column-wise.
    Float results compare with allclose (reduction order differs); integer
    results must match exactly."""
    want = groupby_oracle(table_dict, keys, [(c, o) for c, o in aggs])
    got = out.to_numpy()
    assert sorted(got) == sorted(want), (sorted(got), sorted(want))
    n_groups = len(want[keys[0]])
    assert int(out.row_count) == n_groups
    for name, w in want.items():
        g = got[name]
        assert g.shape == w.astype(g.dtype).shape, name
        if np.issubdtype(g.dtype, np.floating):
            np.testing.assert_allclose(g, w, atol=atol, rtol=1e-4,
                                       err_msg=name)
        else:
            np.testing.assert_array_equal(g, w, err_msg=name)


# --- segment_reduce kernel vs oracle -----------------------------------------


@pytest.mark.parametrize("n,g", [(1, 1), (100, 7), (1024, 128), (5000, 37),
                                 (9999, 1000)])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_segment_reduce_kernel_sweep(n, g, op, dtype):
    vals = jnp.asarray(RNG.integers(-40, 40, n), dtype)
    seg = jnp.asarray(RNG.integers(-1, g, n), jnp.int32)  # -1 = padding
    want = np.asarray(ref.segment_reduce_ref(vals, seg, g, op))
    got = segment_reduce_tiles(vals, seg, g, op)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_segment_reduce_xla_fallback_matches_kernel(op):
    n, g = 3000, 50
    vals = jnp.asarray(RNG.standard_normal(n), jnp.float32)
    seg = jnp.asarray(RNG.integers(-1, g, n), jnp.int32)
    a = np.asarray(kops.segment_reduce(vals, seg, g, op, use_kernel=True))
    b = np.asarray(kops.segment_reduce(vals, seg, g, op, use_kernel=False))
    np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_segment_reduce_nd_payload(op):
    n, g, d = 500, 9, 6
    vals = jnp.asarray(RNG.integers(-40, 40, (n, d)), jnp.int32)
    seg = jnp.asarray(RNG.integers(-1, g, n), jnp.int32)
    got = np.asarray(kops.segment_reduce(vals, seg, g, op))
    want = np.asarray(ref.segment_reduce_ref(vals, seg, g, op))
    np.testing.assert_array_equal(got, want)


def test_segment_reduce_empty_segments_hold_identity():
    vals = jnp.asarray([1.0, 2.0], jnp.float32)
    seg = jnp.asarray([0, 0], jnp.int32)
    out = np.asarray(kops.segment_reduce(vals, seg, 4, "min"))
    assert out[0] == 1.0 and np.all(np.isinf(out[1:]))


@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_segment_reduce_oversize_runs_in_kernel(op):
    # num_segments beyond one VMEM tile now tiles the segment axis in a
    # second grid dimension — the kernel path must match the oracle AND
    # the XLA scatter fallback (use_kernel=False) exactly
    n, g = 4000, kops.MAX_SEGMENTS + 300
    vals = jnp.asarray(RNG.integers(-40, 40, n), jnp.int32)
    seg = jnp.asarray(RNG.integers(-1, g, n), jnp.int32)
    want = np.asarray(ref.segment_reduce_ref(vals, seg, g, op))
    for use_kernel in (None, True, False):
        got = np.asarray(kops.segment_reduce(vals, seg, g, op,
                                             use_kernel=use_kernel))
        np.testing.assert_array_equal(got, want)
    # and the raw tiled kernel agrees on its own
    np.testing.assert_array_equal(
        np.asarray(segment_reduce_tiles(vals, seg, g, op)), want)


# --- local groupby vs oracle -------------------------------------------------


def make_table(n, key_range, pad=5, seed=0, int_payload=True):
    rng = np.random.default_rng(seed)
    cols = {
        "k": rng.integers(0, key_range, n).astype(np.int32),
        "v": (rng.integers(-30, 30, n).astype(np.int32) if int_payload
              else rng.standard_normal(n).astype(np.float32)),
    }
    return cols, Table.from_arrays(cols, capacity=n + pad)


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("key_range", [1, 4, 50])
def test_groupby_randomized(seed, key_range):
    cols, t = make_table(60, key_range, seed=seed)
    out = A.groupby(t, "k", ALL_AGGS)
    check_vs_oracle(out, cols, ["k"], ALL_AGGS)


@pytest.mark.parametrize("use_kernel", [True, False])
def test_groupby_kernel_paths_agree(use_kernel):
    cols, t = make_table(200, 11, seed=3)
    out = A.groupby(t, "k", ALL_AGGS, use_kernel=use_kernel)
    check_vs_oracle(out, cols, ["k"], ALL_AGGS)


def test_groupby_float_payload():
    cols, t = make_table(80, 6, seed=2, int_payload=False)
    aggs = [("v", op) for op in ("sum", "mean", "var", "min", "max", "first")]
    out = A.groupby(t, "k", aggs)
    check_vs_oracle(out, cols, ["k"], aggs)


def test_groupby_empty_table():
    t = Table.empty({"k": jnp.int32, "v": jnp.int32}, capacity=8)
    out = A.groupby(t, "k", [("v", "sum"), ("v", "count")])
    assert int(out.row_count) == 0
    assert out.to_numpy()["v_sum"].shape == (0,)


def test_groupby_all_one_group():
    cols = {"k": np.full(30, 5, np.int32),
            "v": np.arange(30, dtype=np.int32)}
    t = Table.from_arrays(cols, capacity=33)
    out = A.groupby(t, "k", ALL_AGGS)
    check_vs_oracle(out, cols, ["k"], ALL_AGGS)
    assert int(out.row_count) == 1


def test_groupby_multikey():
    rng = np.random.default_rng(11)
    cols = {"a": rng.integers(0, 4, 50).astype(np.int32),
            "b": rng.integers(0, 3, 50).astype(np.int32),
            "v": rng.integers(-9, 9, 50).astype(np.int32)}
    t = Table.from_arrays(cols, capacity=54)
    aggs = [("v", "sum"), ("v", "count"), ("v", "first")]
    out = A.groupby(t, ["a", "b"], aggs)
    check_vs_oracle(out, cols, ["a", "b"], aggs)


def test_groupby_nd_payload():
    """Token-vector payload: per-group element-wise aggregation."""
    rng = np.random.default_rng(4)
    cols = {"k": rng.integers(0, 5, 40).astype(np.int32),
            "v": rng.integers(0, 100, (40, 7)).astype(np.int32)}
    t = Table.from_arrays(cols, capacity=44)
    aggs = [("v", op) for op in ("sum", "min", "max", "mean", "first")]
    out = A.groupby(t, "k", aggs)
    check_vs_oracle(out, cols, ["k"], aggs)


def test_groupby_dict_aggs_and_out_capacity():
    cols, t = make_table(64, 32, seed=9)
    out = A.groupby(t, "k", {"v": ["sum", "mean"]}, out_capacity=8)
    assert out.capacity == 8
    assert int(out.row_count) <= 8  # overflow truncates, like join
    # kept groups (key order) match the untruncated result exactly
    full = A.groupby(t, "k", {"v": ["sum", "mean"]})
    fa, tr = full.to_numpy(), out.to_numpy()
    n = int(out.row_count)
    for name in tr:
        np.testing.assert_array_equal(tr[name][:n], fa[name][:n],
                                      err_msg=name)


def test_groupby_kernel_on_large_table_via_out_capacity():
    """out_capacity bounds the segment count, so low-cardinality groupby
    rides the Pallas kernel even when the table itself is large."""
    cols, t = make_table(3000, 12, seed=13)
    out = A.groupby(t, "k", ALL_AGGS, out_capacity=64, use_kernel=True)
    check_vs_oracle(out, cols, ["k"], ALL_AGGS)


def test_segment_reduce_forced_kernel_shape_mismatch_still_raises():
    # oversize segment counts now run in the kernel via segment-axis
    # tiling (see above); a shape/dtype the kernel can never take errors
    with pytest.raises(ValueError, match="1-D"):
        kops.segment_reduce(jnp.zeros((8, 2), jnp.float32),
                            jnp.zeros((8,), jnp.int32), 4, "sum",
                            use_kernel=True)


# --- two-phase decomposition (the distributed combine path, run locally) ------


@pytest.mark.parametrize("n_parts", [1, 3])
def test_partial_combine_equals_direct(n_parts):
    cols, t = make_table(90, 7, seed=6)
    direct = A.groupby(t, "k", ALL_AGGS)
    # split rows into contiguous chunks = "shards" in global row order
    bounds = np.linspace(0, 90, n_parts + 1).astype(int)
    parts = []
    for i in range(n_parts):
        sub = {k: v[bounds[i]:bounds[i + 1]] for k, v in cols.items()}
        parts.append(Table.from_arrays(sub, capacity=len(sub["k"]) + 3))
    partials = [A.partial_groupby(p, "k", ALL_AGGS) for p in parts]
    cat = partials[0]
    for p in partials[1:]:
        cat = concat_tables(cat, p)
    combined = A.combine_groupby(cat, "k", ALL_AGGS)
    da, db = direct.to_numpy(), combined.to_numpy()
    assert sorted(da) == sorted(db)
    for name in da:
        if np.issubdtype(da[name].dtype, np.floating):
            np.testing.assert_allclose(da[name], db[name], atol=1e-4,
                                       rtol=1e-4, err_msg=name)
        else:
            np.testing.assert_array_equal(da[name], db[name], err_msg=name)
    check_vs_oracle(combined, cols, ["k"], ALL_AGGS)


def test_partial_groupby_shrinks_rows():
    """The two-phase win: partials carry <= cardinality rows per shard."""
    cols, t = make_table(500, 8, seed=1)
    part = A.partial_groupby(t, "k", [("v", "mean")], out_capacity=16)
    assert part.capacity == 16
    assert int(part.row_count) == len(set(cols["k"].tolist()))


# --- concat_tables edge cases (zero-valid-row inputs) -------------------------


def _kv(n, cap, seed=0):
    rng = np.random.default_rng(seed)
    return Table.from_arrays(
        {"k": rng.integers(0, 9, n).astype(np.int32)}, capacity=cap)


def test_concat_empty_left():
    a = Table.empty({"k": jnp.int32}, capacity=4)
    b = _kv(3, 5, seed=1)
    out = concat_tables(a, b)
    assert int(out.row_count) == 3
    np.testing.assert_array_equal(out.to_numpy()["k"], b.to_numpy()["k"])


def test_concat_empty_right():
    a = _kv(3, 5, seed=2)
    b = Table.empty({"k": jnp.int32}, capacity=4)
    out = concat_tables(a, b)
    assert int(out.row_count) == 3
    np.testing.assert_array_equal(out.to_numpy()["k"], a.to_numpy()["k"])


def test_concat_both_empty():
    a = Table.empty({"k": jnp.int32}, capacity=4)
    b = Table.empty({"k": jnp.int32}, capacity=2)
    out = concat_tables(a, b)
    assert int(out.row_count) == 0
    assert out.capacity == 6
    assert out.to_numpy()["k"].shape == (0,)


def test_concat_empty_then_groupby():
    """Zero-valid concat feeding groupby (the pipeline stats path)."""
    a = Table.empty({"k": jnp.int32, "v": jnp.int32}, capacity=4)
    cols = {"k": np.asarray([1, 1, 2], np.int32),
            "v": np.asarray([10, 20, 30], np.int32)}
    b = Table.from_arrays(cols, capacity=6)
    out = A.groupby(concat_tables(a, b), "k", [("v", "sum"), ("v", "count")])
    check_vs_oracle(out, cols, ["k"], [("v", "sum"), ("v", "count")])


# --- TPC-H Q1's shape through the serving path, sized from statistics ---------

Q1_KEYS = ["returnflag", "linestatus"]
Q1_AGGS = [("quantity", "sum"), ("extendedprice", "sum"),
           ("disc_price", "sum"), ("charge", "sum"), ("quantity", "mean"),
           ("extendedprice", "mean"), ("discount", "mean"),
           ("quantity", "count")]


def q1_table(n, seed=0):
    """A lineitem-shaped table at Q1's magnitudes (3 x 2 flag codes)."""
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 51, n).astype(np.float32)
    price = np.round(qty * rng.uniform(900.0, 2100.0, n), 2)
    disc = rng.integers(0, 11, n) / 100.0
    tax = rng.integers(0, 9, n) / 100.0
    return {"returnflag": rng.integers(0, 3, n).astype(np.int32),
            "linestatus": rng.integers(0, 2, n).astype(np.int32),
            "shipdate": rng.integers(0, 2526, n).astype(np.int32),
            "quantity": qty,
            "extendedprice": price.astype(np.float32),
            "discount": disc.astype(np.float32),
            "disc_price": (price * (1 - disc)).astype(np.float32),
            "charge": (price * (1 - disc) * (1 + tax)).astype(np.float32)}


def test_q1_through_serving_matches_oracle():
    from repro.core.context import DistContext
    from repro.core.serving import ServingSession

    cols = q1_table(1 << 18)
    session = ServingSession(DistContext())
    session.register("lineitem", Table.from_arrays(cols), analyze=True)
    last = 2436
    got = (session.frame("lineitem")
           .select(lambda c: c["shipdate"] <= last, key=("q1<=", last))
           .groupby(Q1_KEYS, Q1_AGGS).collect().to_table().to_numpy())
    keep = cols["shipdate"] <= last
    # the oracle sums the same float32 values in float64
    kept = {k: (v[keep].astype(np.float64) if v.dtype == np.float32
                else v[keep]) for k, v in cols.items()}
    want = groupby_oracle(kept, Q1_KEYS, Q1_AGGS)
    assert len(got["returnflag"]) == 6
    for k in Q1_KEYS + ["quantity_count"]:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    for name, w in want.items():
        if name not in Q1_KEYS + ["quantity_count"]:
            np.testing.assert_allclose(got[name], w, rtol=1e-6, atol=0,
                                       err_msg=name)


def _with_ndv(dt, ndv):
    """``dt`` whose statistics claim ``ndv`` distinct keys ``k``."""
    import dataclasses

    from repro.core import stats as S

    stats = S.TableStats(rows=dt.stats.rows,
                         columns=(("k", S.ColumnStats(float(ndv))),),
                         max_shard_rows=dt.stats.max_shard_rows)
    return dataclasses.replace(dt, stats=stats)


def test_sized_groupby_underestimate_retries_once_safe():
    from repro.core.context import DistContext

    ctx = DistContext()
    cols, t = make_table(600, 200, pad=0, seed=3)
    aggs = (("v", "sum"), ("v", "count"))
    dt = _with_ndv(ctx.analyze(ctx.scatter(t)), 2)
    frame = ctx.frame(dt).groupby("k", aggs)
    plan = frame.optimized()
    assert plan.out_sized and plan.out_capacity < 200
    got = frame.collect()
    assert ctx.overflow_retries == 1
    check_vs_oracle(got.to_table(), cols, ["k"], aggs)
    # a capacity the user set truncates, with no retry
    cut = ctx.frame(dt).groupby("k", aggs, out_capacity=5).collect()
    assert ctx.overflow_retries == 1
    assert int(cut.global_rows()) == 5
