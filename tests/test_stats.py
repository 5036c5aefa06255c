"""Statistics layer: NDV sketch accuracy, sizing math, analyze() contract.

Deliberately hypothesis-free: part of the minimal-environment tier-1 gate.
"""
import numpy as np
import pytest

from repro.core import stats as S
from repro.core.context import DistContext
from repro.core.table import Table


# --- sketch / linear counting -------------------------------------------------


@pytest.mark.parametrize("ndv", [1, 16, 200, 2000])
def test_analyze_table_ndv_accuracy(ndv):
    rng = np.random.default_rng(ndv)
    t = Table.from_arrays({
        "k": rng.integers(0, ndv, 8000).astype(np.int32)})
    true_ndv = len(np.unique(np.asarray(t.columns["k"])[:8000]))
    st = S.analyze_table(t)
    got = st.col("k").ndv
    assert abs(got - true_ndv) <= max(4.0, 0.15 * true_ndv), (got, true_ndv)


def test_analyze_table_min_max_and_rows():
    t = Table.from_arrays({
        "k": np.asarray([5, -3, 9, 9], np.int32),
        "v": np.asarray([1.5, -2.5, 0.0, 3.0], np.float32)}, capacity=10)
    st = S.analyze_table(t)
    assert st.rows == 4.0
    assert st.col("k").lo == -3.0 and st.col("k").hi == 9.0
    assert st.col("v").lo == -2.5 and st.col("v").hi == 3.0
    # garbage rows past row_count must not leak into the sketch
    assert st.col("k").ndv <= 4.0 + 1e-6


def test_linear_count_saturation_and_empty():
    assert S.linear_count(0, 0) == 0.0
    assert S.linear_count(0, 100) == 0.0
    # saturated bitmap: every value looks distinct -> clamp to rows
    assert S.linear_count(S.SKETCH_BUCKETS, 10_000) == 10_000.0
    assert S.linear_count(10, 5) <= 5.0  # never exceeds the row count


# --- TableStats algebra -------------------------------------------------------


def test_joint_ndv_caps_and_unknown_columns():
    st = S.TableStats(rows=1000.0, columns=(
        ("a", S.ColumnStats(50.0)), ("b", S.ColumnStats(40.0))))
    assert st.ndv(("a",)) == 50.0
    assert st.ndv(("a", "b")) == 1000.0  # 50*40 capped by rows
    assert st.ndv(("a", "missing")) is None  # unknown column poisons joint


def test_cap_rows_caps_column_ndv_and_filters():
    st = S.TableStats(rows=1000.0, columns=(
        ("a", S.ColumnStats(500.0, 0.0, 9.0)), ("b", S.ColumnStats(40.0))))
    out = S.cap_rows(st, 100.0, keep=("a",))
    assert out.rows == 100.0
    assert out.col("a").ndv == 100.0  # 500 capped to the new row count
    assert out.col("a").lo == 0.0 and out.col("a").hi == 9.0
    assert out.col("b") is None
    assert out.max_shard_rows is None  # placement knowledge doesn't survive


# --- sizing math --------------------------------------------------------------


def test_with_skew_margin_properties():
    assert S.with_skew_margin(0.0) >= 1  # never a zero-capacity bucket
    assert S.with_skew_margin(100.0) > 100  # mean alone is not enough
    # margin is sublinear: large buckets approach the mean
    assert S.with_skew_margin(10_000.0) < 1.1 * 10_000


def test_size_bucket_beats_fallback_slack_at_scale():
    # the whole point: estimated occupancy << capacity-based fallback
    p, cap, rows = 8, 4000, 2000  # half-full table
    from repro.core.repartition import default_bucket_capacity
    fallback = default_bucket_capacity(cap, p)  # FALLBACK_SLACK path
    sized = S.size_bucket(rows / p, p)
    assert sized < fallback, (sized, fallback)


def test_fallback_slack_is_the_single_source():
    # the documented no-stats constant feeds default_bucket_capacity
    from repro.core.repartition import default_bucket_capacity
    assert default_bucket_capacity(1000, 8) == \
        default_bucket_capacity(1000, 8, slack=S.FALLBACK_SLACK)


# --- DistContext.analyze ------------------------------------------------------


@pytest.fixture(scope="module")
def ctx():
    return DistContext(axis_name="stats_test")


def test_analyze_exact_rows_and_idempotence(ctx):
    rng = np.random.default_rng(3)
    t = Table.from_arrays({
        "k": rng.integers(0, 64, 500).astype(np.int32),
        "d0": rng.standard_normal(500).astype(np.float32)}, capacity=600)
    dt = ctx.scatter(t)
    assert dt.stats is None
    a = ctx.analyze(dt)
    assert a.stats is not None and a.stats.rows == 500.0
    assert a.stats.max_shard_rows is not None
    assert ctx.analyze(a) is a  # cached: second analyze is free
    true_ndv = len(np.unique(np.asarray(t.columns["k"])[:500]))
    assert abs(a.stats.col("k").ndv - true_ndv) <= max(4.0, 0.15 * true_ndv)


def test_default_mesh_is_auto_and_analyze_runs():
    # jax.make_mesh defaults to Explicit axes, which type every global
    # array by its sharding and reject the plain gathers/scatters of the
    # analyze sweep; the context's default mesh must be Auto
    from jax.sharding import AxisType

    ctx = DistContext()
    assert ctx.mesh.axis_types == (AxisType.Auto,)
    t = Table.from_arrays({"k": np.arange(40, dtype=np.int32) % 7},
                          capacity=48)
    a = ctx.analyze(ctx.scatter(t))
    assert a.stats.rows == 40.0
    assert a.stats.col("k").lo == 0.0 and a.stats.col("k").hi == 6.0


def test_analyze_skips_nd_payload_columns(ctx):
    t = Table.from_arrays({
        "k": np.arange(8, dtype=np.int32),
        "tokens": np.zeros((8, 16), np.int32)})
    a = ctx.analyze(ctx.scatter(t))
    assert a.stats.col("k") is not None
    assert a.stats.col("tokens") is None  # N-D: no placement/sketch role


def test_collect_propagates_estimated_stats(ctx):
    rng = np.random.default_rng(9)
    t = Table.from_arrays({
        "k": rng.integers(0, 16, 300).astype(np.int32),
        "d0": rng.integers(-5, 5, 300).astype(np.float32)})
    dt = ctx.analyze(ctx.scatter(t))
    out = ctx.frame(dt).groupby("k", (("d0", "sum"),)).collect()
    assert out.stats is not None
    # NDV-capped output estimate: ~16 groups, never the input row count
    assert out.stats.rows <= 32.0
    # unanalyzed inputs propagate nothing
    out2 = ctx.frame(ctx.scatter(t)).groupby("k", (("d0", "sum"),)).collect()
    assert out2.stats is None
