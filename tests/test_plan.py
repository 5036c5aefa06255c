"""Logical-plan layer: golden optimizer shapes + fused == eager execution.

Golden tests drive the optimizer passes offline (pure plan-to-plan, an
explicit num_shards — no mesh needed) and assert the rewrites actually
fire: projection/predicate pushdown below the shuffle boundaries, shuffle
elision from Partitioning tags. Execution tests run fused LazyFrame chains
on the single-device context and compare against the eager op-by-op result
(which keeps its shuffles — the two paths exercise different programs).

Deliberately hypothesis-free: part of the minimal-environment tier-1 gate.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as PL
from repro.core.context import DistContext
from repro.core.repartition import Partitioning, RangePartitioning
from repro.core.table import Table

I32, F32 = jnp.dtype(jnp.int32), jnp.dtype(jnp.float32)

ORDERS = {"k": jax.ShapeDtypeStruct((), I32),
          "d0": jax.ShapeDtypeStruct((), F32),
          "d1": jax.ShapeDtypeStruct((), F32)}
USERS = {"k": jax.ShapeDtypeStruct((), I32),
         "d0": jax.ShapeDtypeStruct((), F32),
         "v0": jax.ShapeDtypeStruct((), F32)}


def find(node, cls):
    """All nodes of type `cls` in depth-first order."""
    out = [node] if isinstance(node, cls) else []
    for c in PL.children(node):
        out += find(c, cls)
    return out


# --- golden plan-shape tests --------------------------------------------------


def test_projection_pushdown_narrows_join_inputs():
    plan = PL.GroupBy(PL.Join(PL.Scan(0), PL.Scan(1), ("k",)),
                      ("k",), (("d0", "sum"),))
    opt = PL.optimize(plan, [ORDERS, USERS], num_shards=8)
    join = find(opt, PL.Join)[0]
    assert isinstance(join.left, PL.Project)
    assert set(join.left.columns) == {"k", "d0"}  # d1 dropped pre-shuffle
    assert isinstance(join.right, PL.Project)
    # right d0 would surface as the unused d0_r: only the key survives
    assert set(join.right.columns) == {"k"}


def test_predicate_pushdown_below_join_left():
    pred = lambda c: c["d0"] > 0.5
    plan = PL.Select(PL.Join(PL.Scan(0), PL.Scan(1), ("k",), how="inner"),
                     pred, key="p")
    opt = PL.optimize(plan, [ORDERS, USERS], num_shards=8)
    assert isinstance(opt, PL.Join)  # select no longer on top
    selects = find(opt.left, PL.Select)
    assert selects and selects[0].columns == ("d0",)
    assert not find(opt.right, PL.Select)


def test_predicate_pushdown_blocked_for_full_join():
    plan = PL.Select(PL.Join(PL.Scan(0), PL.Scan(1), ("k",), how="full"),
                     lambda c: c["d0"] > 0.5, key="p")
    opt = PL.optimize(plan, [ORDERS, USERS], num_shards=8)
    # pushing a one-sided filter through a full outer join is unsound
    assert isinstance(opt, PL.Select)


def test_predicate_pushdown_below_sort_and_project():
    plan = PL.Select(PL.Sort(PL.Project(PL.Scan(0), ("k", "d0")), ("k",)),
                     lambda c: c["d0"] > 0.0, key="p")
    opt = PL.optimize(plan, [ORDERS], num_shards=8)
    assert isinstance(opt, PL.Sort)
    assert find(opt, PL.Select), "select should sink below the sort shuffle"


def test_probe_unprobeable_predicate_pins_select():
    # reads via values() — the recorder sees no key access, footprint None
    plan = PL.Select(PL.Join(PL.Scan(0), PL.Scan(1), ("k",)),
                     lambda c: list(c.values())[0] > 0, key="p")
    opt = PL.optimize(plan, [ORDERS, USERS], num_shards=8)
    assert isinstance(opt, PL.Select) and opt.columns is None


def test_shuffle_elision_co_partitioned_join():
    part = Partitioning(("k",), 8, 7)
    plan = PL.Join(PL.Scan(0, partitioning=part),
                   PL.Scan(1, partitioning=part), ("k",))
    opt = PL.optimize(plan, [ORDERS, USERS], num_shards=8)
    join = find(opt, PL.Join)[0]
    assert join.skip_left_shuffle and join.skip_right_shuffle


def test_shuffle_elision_one_side_adopts_other_seed():
    part = Partitioning(("k",), 8, 3)  # non-default seed
    plan = PL.Join(PL.Scan(0, partitioning=part), PL.Scan(1), ("k",), seed=7)
    opt = PL.optimize(plan, [ORDERS, USERS], num_shards=8)
    join = find(opt, PL.Join)[0]
    assert join.skip_left_shuffle and not join.skip_right_shuffle
    assert join.shuffle_seed == 3  # right side reshuffles INTO the tag


def test_groupby_elides_after_join_on_same_key():
    plan = PL.GroupBy(PL.Join(PL.Scan(0), PL.Scan(1), ("k",)),
                      ("k",), (("d0", "sum"),))
    opt = PL.optimize(plan, [ORDERS, USERS], num_shards=8)
    assert opt.skip_shuffle  # join output is already partitioned on k
    join = find(opt, PL.Join)[0]
    assert not (join.skip_left_shuffle or join.skip_right_shuffle)


def test_outer_join_output_carries_no_partitioning():
    # unmatched-side rows of right/full joins have zero-filled key columns,
    # so a downstream groupby must NOT elide its shuffle on the join's keys
    for how in ("right", "full"):
        plan = PL.GroupBy(PL.Join(PL.Scan(0), PL.Scan(1), ("k",), how=how),
                          ("k",), (("d0", "sum"),))
        opt = PL.optimize(plan, [ORDERS, USERS], num_shards=8)
        assert not opt.skip_shuffle, how
        assert PL.output_partitioning(
            PL.Join(PL.Scan(0), PL.Scan(1), ("k",), how=how),
            [ORDERS, USERS], 8) is None, how
    # inner and left keep their true keys on the hash shard: tag survives
    for how in ("inner", "left"):
        assert PL.output_partitioning(
            PL.Join(PL.Scan(0), PL.Scan(1), ("k",), how=how),
            [ORDERS, USERS], 8) is not None, how


def test_projection_pushdown_keeps_collision_for_suffixed_column():
    # consuming d0_r (right's d0, suffixed only WHILE the name clashes)
    # must keep left's otherwise-dead d0 alive below the join
    plan = PL.GroupBy(PL.Join(PL.Scan(0), PL.Scan(1), ("k",)),
                      ("k",), (("d0_r", "max"),))
    opt = PL.optimize(plan, [ORDERS, USERS], num_shards=8)
    join = find(opt, PL.Join)[0]
    assert "d0" in join.left.columns
    assert "d0" in join.right.columns


def test_projection_dropping_key_kills_partitioning():
    part = Partitioning(("k",), 8, 7)
    plan = PL.GroupBy(PL.Project(PL.Scan(0, partitioning=part), ("d0",)),
                      ("d0",), (("d0", "count"),))
    opt = PL.optimize(plan, [ORDERS], num_shards=8)
    assert not opt.skip_shuffle  # tag does not survive losing its key column


def test_mismatched_modulus_blocks_elision():
    part = Partitioning(("k",), 4, 7)  # partitioned for a 4-shard mesh
    plan = PL.GroupBy(PL.Scan(0, partitioning=part), ("k",),
                      (("d0", "sum"),))
    opt = PL.optimize(plan, [ORDERS], num_shards=8)
    assert not opt.skip_shuffle


def test_range_tag_not_equal_to_hash_tag():
    # RangePartitioning is a dataclass precisely so coincident fields never
    # tuple-compare equal to a hash Partitioning (NamedTuple == tuple)
    assert Partitioning(("k",), 8, 7) != RangePartitioning(("k",), 8, 7)
    assert RangePartitioning(("k",), 8, 7) != Partitioning(("k",), 8, 7)


def test_sort_join_range_aligns_one_side():
    # the tentpole golden shape: sort(k) -> join(on=k) keeps the sorted
    # side in place and range-aligns the other — ONE AllToAll for the join
    plan = PL.Join(PL.Sort(PL.Scan(0), ("k",)), PL.Scan(1), ("k",),
                   algorithm="sort")
    opt = PL.optimize(plan, [ORDERS, USERS], num_shards=8)
    assert opt.skip_left_shuffle and not opt.skip_right_shuffle
    assert opt.align == "left" and opt.align_keys == ("k",)
    assert "align=left" in PL.explain(opt)
    # mirrored: the sorted side on the right
    plan = PL.Join(PL.Scan(0), PL.Sort(PL.Scan(1), ("k",)), ("k",))
    opt = PL.optimize(plan, [ORDERS, USERS], num_shards=8)
    assert opt.skip_right_shuffle and opt.align == "right"


def test_sort_join_range_alignment_key_prefix_only():
    # range keys must be a PREFIX of the join keys (placement is a function
    # of the prefix); sort on a non-prefix key must not elide
    plan = PL.Join(PL.Sort(PL.Scan(0), ("d0",)), PL.Scan(1), ("k",))
    opt = PL.optimize(plan, [ORDERS, USERS], num_shards=8)
    assert opt.align is None
    assert not opt.skip_left_shuffle and not opt.skip_right_shuffle


def test_sort_output_partitioning_and_groupby_elision():
    part = PL.output_partitioning(PL.Sort(PL.Scan(0), ("k", "d0")),
                                  [ORDERS], 8)
    assert isinstance(part, RangePartitioning)
    assert part.keys == ("k", "d0") and part.num_partitions == 8
    # groupby on keys that EXTEND the range prefix elides (prefix rule) ...
    plan = PL.GroupBy(PL.Sort(PL.Scan(0), ("k",)), ("k", "d1"),
                      (("d0", "sum"),))
    assert PL.optimize(plan, [ORDERS], 8).skip_shuffle
    # ... but a range partitioning on (k, d0) does NOT satisfy keys (k,):
    # equal k can straddle shards when d0 differs
    plan = PL.GroupBy(PL.Sort(PL.Scan(0), ("k", "d0")), ("k",),
                      (("d1", "sum"),))
    assert not PL.optimize(plan, [ORDERS], 8).skip_shuffle


def test_sort_sort_elision_both_directions():
    # by a prefix of the range keys, and by an extension of them
    for outer in (("k",), ("k", "d0", "d1")):
        plan = PL.Sort(PL.Sort(PL.Scan(0), ("k", "d0")), outer)
        opt = PL.optimize(plan, [ORDERS], num_shards=8)
        assert opt.skip_shuffle, outer
    plan = PL.Sort(PL.Sort(PL.Scan(0), ("d0",)), ("k",))
    assert not PL.optimize(plan, [ORDERS], 8).skip_shuffle


def test_limit_preserves_range_tag_project_kills_it():
    # limit only drops rows: the surviving placement still satisfies a
    # downstream groupby; projecting a range key away kills the tag
    plan = PL.GroupBy(PL.Limit(PL.Sort(PL.Scan(0), ("k",)), 10), ("k",),
                      (("d0", "sum"),))
    assert PL.optimize(plan, [ORDERS], 8).skip_shuffle
    plan = PL.GroupBy(
        PL.Project(PL.Sort(PL.Scan(0), ("k",)), ("d0",)),
        ("d0",), (("d0", "count"),))
    assert not PL.optimize(plan, [ORDERS], 8).skip_shuffle


def test_scan_range_tag_from_materialized_sort():
    # a Scan carrying a RangePartitioning (eager ctx.sort output) feeds the
    # same elision rules as a plan-internal Sort
    part = RangePartitioning(("k",), 8, ("table", 999))
    plan = PL.GroupBy(PL.Scan(0, partitioning=part), ("k",),
                      (("d0", "sum"),))
    assert PL.optimize(plan, [ORDERS], 8).skip_shuffle
    # mismatched modulus: dropped, shuffle stays
    part4 = RangePartitioning(("k",), 4, ("table", 999))
    plan = PL.GroupBy(PL.Scan(0, partitioning=part4), ("k",),
                      (("d0", "sum"),))
    assert not PL.optimize(plan, [ORDERS], 8).skip_shuffle


def test_self_join_same_range_fingerprint_skips_both():
    part = RangePartitioning(("k",), 8, ("table", 7))
    plan = PL.Join(PL.Scan(0, partitioning=part),
                   PL.Scan(1, partitioning=part), ("k",))
    opt = PL.optimize(plan, [ORDERS, USERS], num_shards=8)
    assert opt.skip_left_shuffle and opt.skip_right_shuffle
    assert opt.align is None
    # different fingerprints = different splitters: align, don't skip both
    other = RangePartitioning(("k",), 8, ("table", 8))
    plan = PL.Join(PL.Scan(0, partitioning=part),
                   PL.Scan(1, partitioning=other), ("k",))
    opt = PL.optimize(plan, [ORDERS, USERS], num_shards=8)
    assert opt.skip_left_shuffle and not opt.skip_right_shuffle
    assert opt.align == "left"


def test_single_shard_elides_everything():
    plan = PL.Sort(PL.GroupBy(PL.Join(PL.Scan(0), PL.Scan(1), ("k",)),
                              ("k",), (("d0", "sum"),)), ("k",))
    opt = PL.optimize(plan, [ORDERS, USERS], num_shards=1)
    assert "alltoall" not in PL.explain(opt)


def test_canonical_key_stability_and_uncacheable_select():
    mk = lambda: PL.GroupBy(PL.Join(PL.Scan(0), PL.Scan(1), ("k",)),
                            ("k",), (("d0", "sum"),))
    assert PL.canonical_key(mk()) == PL.canonical_key(mk())
    assert PL.canonical_key(
        PL.Select(mk(), lambda c: c["d0"] > 0)) is None  # no key -> no cache
    k1 = PL.canonical_key(PL.Select(mk(), lambda c: c["d0"] > 0, key="a"))
    k2 = PL.canonical_key(PL.Select(mk(), lambda c: c["d0"] > 1, key="b"))
    assert k1 is not None and k1 != k2


# --- execution: fused == eager on the single-device context -------------------


@pytest.fixture(scope="module")
def ctx():
    return DistContext(axis_name="plan_test")


def int_table(n, key_range, seed, names=("d0", "d1")):
    rng = np.random.default_rng(seed)
    cols = {"k": rng.integers(0, key_range, n).astype(np.int32)}
    for nm in names:
        # integer-valued floats: aggregation order cannot perturb bits
        cols[nm] = rng.integers(-40, 40, n).astype(np.float32)
    return Table.from_arrays(cols)


def assert_tables_equal(a, b):
    from repro.testing.compare import table_rows, tables_bitwise_equal
    assert tables_bitwise_equal(a, b), (table_rows(a), table_rows(b))


@pytest.mark.parametrize("seed", [0, 1])
def test_collect_matches_eager_join_select_groupby(ctx, seed):
    orders = ctx.scatter(int_table(300, 500, seed))
    users = ctx.scatter(int_table(300, 500, seed + 50))
    aggs = (("d0", "sum"), ("d0", "mean"), ("d0", "count"), ("d0_r", "max"))

    j, _ = ctx.join(orders, users, "k")
    s = ctx.select(j, lambda c: c["d0"] > 0.0, key="pos")
    ge, _ = ctx.groupby(s, "k", aggs, strategy="shuffle")

    fused = (ctx.frame(orders).join(ctx.frame(users), "k")
             .select(lambda c: c["d0"] > 0.0, key="pos")
             .groupby("k", aggs, strategy="shuffle"))
    assert_tables_equal(ge, fused.collect())


def test_collect_matches_eager_outer_join_groupby(ctx):
    # the review repro: fused full-join -> groupby must match eager
    a = ctx.scatter(int_table(150, 80, 61))
    b = ctx.scatter(int_table(150, 80, 62))
    j, _ = ctx.join(a, b, "k", how="full")
    ge, _ = ctx.groupby(j, "k", (("d0", "count"),), strategy="shuffle")
    fused = (ctx.frame(a).join(ctx.frame(b), "k", how="full")
             .groupby("k", (("d0", "count"),), strategy="shuffle"))
    assert_tables_equal(ge, fused.collect())


def test_collect_suffixed_column_aggregation(ctx):
    # the review repro: aggregating d0_r after projection pushdown
    a = ctx.scatter(int_table(150, 60, 63))
    b = ctx.scatter(int_table(150, 60, 64))
    j, _ = ctx.join(a, b, "k")
    ge, _ = ctx.groupby(j, "k", (("d0_r", "max"),), strategy="shuffle")
    fused = (ctx.frame(a).join(ctx.frame(b), "k")
             .groupby("k", (("d0_r", "max"),), strategy="shuffle"))
    assert_tables_equal(ge, fused.collect())


def test_collect_matches_eager_set_ops(ctx):
    a = ctx.scatter(int_table(120, 40, 3, names=()))
    b = ctx.scatter(int_table(120, 40, 4, names=()))
    for eager, frame in [
        (ctx.union(a, b)[0], ctx.frame(a).union(ctx.frame(b))),
        (ctx.intersect(a, b)[0], ctx.frame(a).intersect(ctx.frame(b))),
        (ctx.difference(a, b)[0], ctx.frame(a).difference(ctx.frame(b))),
        (ctx.distinct(a)[0], ctx.frame(a).distinct()),
    ]:
        assert_tables_equal(eager, frame.collect())


def test_multikey_sort_matches_lexsort(ctx):
    t = int_table(200, 12, 7)  # many key ties -> d0 breaks them
    s, _ = ctx.sort(ctx.scatter(t), ["k", "d0"])
    got = s.to_table().to_numpy()
    d = t.to_numpy()
    order = np.lexsort((d["d0"], d["k"]))  # primary key last in lexsort
    np.testing.assert_array_equal(got["k"], d["k"][order])
    np.testing.assert_array_equal(got["d0"], d["d0"][order])


def test_lazy_sort_and_limit(ctx):
    t = int_table(150, 30, 11)
    out = ctx.frame(ctx.scatter(t)).sort(["k", "d0"]).limit(10).collect()
    d = out.to_table().to_numpy()
    ref = t.to_numpy()
    order = np.lexsort((ref["d0"], ref["k"]))
    np.testing.assert_array_equal(d["k"], ref["k"][order][:10])


def test_global_limit_matches_oracle(ctx):
    # limit(n) == the first n rows of the global table, for every n regime
    t = int_table(120, 40, 71)
    dt = ctx.scatter(t)
    ref = t.to_numpy()
    for n in (0, 1, 13, 120, 200):
        d = ctx.limit(dt, n).to_table().to_numpy()
        assert len(d["k"]) == min(n, 120), n
        np.testing.assert_array_equal(d["k"], ref["k"][:n])
        lazy = ctx.frame(dt).limit(n).collect().to_table().to_numpy()
        np.testing.assert_array_equal(lazy["k"], ref["k"][:n])


def test_fused_sort_join_matches_eager(ctx):
    # sort -> sort-merge join: the range fast path vs eager's re-shuffles
    a = ctx.scatter(int_table(200, 300, 81))
    b = ctx.scatter(int_table(200, 300, 82))
    s, _ = ctx.sort(a, "k")
    eager, _ = ctx.join(s, b, "k", algorithm="sort")
    fused = (ctx.frame(a).sort("k")
             .join(ctx.frame(b), "k", algorithm="sort"))
    assert_tables_equal(eager, fused.collect())


def test_eager_sort_tag_rides_frame_boundary(ctx):
    # ctx.sort tags its output; a frame over it elides the groupby shuffle
    s, _ = ctx.sort(ctx.scatter(int_table(150, 30, 91)), "k")
    assert isinstance(s.partitioning, RangePartitioning)
    assert s.partitioning.keys == ("k",)
    f = ctx.frame(s).groupby("k", (("d0", "sum"),))
    assert all(r["elided"] for r in f.plan_report())
    eager, _ = ctx.groupby(s, "k", (("d0", "sum"),))
    assert_tables_equal(eager, f.collect())
    # two materializations never share splitter provenance
    s2, _ = ctx.sort(ctx.scatter(int_table(150, 30, 92)), "k")
    assert s.partitioning.fingerprint != s2.partitioning.fingerprint


def test_plan_report_attributes_limit_at_zero_bytes(ctx):
    rep = (ctx.frame(ctx.scatter(int_table(64, 16, 93)))
           .sort("k").limit(5).plan_report())
    ops = [r["op"] for r in rep]
    assert "sort" in ops and "limit" in ops, ops
    lim = rep[ops.index("limit")]
    assert lim["elided"] and lim["wire_bytes"] == 0 and lim["bucket"] == 0


def test_co_partitioned_fast_path_matches_shuffled(ctx):
    # partition_by tags its output; the tagged join must equal the untagged
    raw = ctx.scatter(int_table(200, 64, 21))
    dims = ctx.scatter(int_table(64, 64, 22, names=("v0",)))
    part_raw, _ = ctx.partition_by(raw, "k")
    part_dims, _ = ctx.partition_by(dims, "k")
    assert part_raw.partitioning == Partitioning(("k",), ctx.num_shards, 7)
    fast = ctx.frame(part_raw).join(ctx.frame(part_dims), "k")
    rep = fast.plan_report()
    assert all(r["elided"] for r in rep), rep
    slow, _ = ctx.join(raw, dims, "k")
    assert_tables_equal(slow, fast.collect())


def test_plan_report_accounts_wire_bytes(ctx):
    orders = ctx.scatter(int_table(100, 50, 31))
    users = ctx.scatter(int_table(100, 50, 32))
    f = (ctx.frame(orders).join(ctx.frame(users), "k", bucket_capacity=64)
         .groupby("k", (("d0", "sum"),)))
    rep = f.plan_report()
    assert len(rep) == 3  # join L, join R, groupby
    assert [r["elided"] for r in rep].count(True) >= 1  # groupby elides
    p = ctx.num_shards
    for r in rep:
        expect = 0 if r["elided"] else p * p * r["bucket"] * r["row_bytes"]
        assert r["wire_bytes"] == expect


def test_select_cache_key_controls_recompilation(ctx):
    t = ctx.scatter(int_table(64, 16, 41))
    n0 = len(ctx.plan_cache)
    ctx.select(t, lambda c: c["d0"] > 0, key="cached_pred")
    n1 = len(ctx.plan_cache)
    assert n1 == n0 + 1
    ctx.select(t, lambda c: c["d0"] > 0, key="cached_pred")
    assert len(ctx.plan_cache) == n1  # hit
    # keyless: cached under a code-identity key — one entry, and a
    # re-created lambda from the same definition site HITS it
    def keyless():
        return ctx.select(t, lambda c: c["d0"] < 0)

    keyless()
    assert len(ctx.plan_cache) == n1 + 1
    hits = ctx.cache_stats()["hits"]
    keyless()
    assert len(ctx.plan_cache) == n1 + 1
    assert ctx.cache_stats()["hits"] == hits + 1


def test_same_key_different_predicate_not_conflated(ctx):
    # the bytecode fingerprint keeps a reused key from serving stale code
    t = ctx.scatter(int_table(64, 16, 51))
    a = ctx.select(t, lambda c: c["d0"] > 0, key="same")
    b = ctx.select(t, lambda c: c["d0"] < 0, key="same")
    da, db = a.to_table().to_numpy(), b.to_table().to_numpy()
    assert (da["d0"] > 0).all()
    assert (db["d0"] < 0).all()


def test_collect_caches_on_canonical_plan(ctx):
    t = ctx.scatter(int_table(64, 16, 43))
    f = lambda: (ctx.frame(t)
                 .select(lambda c: c["d0"] > 0, key="q")
                 .groupby("k", (("d0", "sum"),)))
    f().collect()
    n1 = len(ctx.plan_cache)
    f().collect()  # same canonical plan + shapes -> cache hit
    assert len(ctx.plan_cache) == n1


# --- cost model: limit pushdown, strategy choice, capacity sizing -------------


from repro.core import stats as S  # noqa: E402  (groups the cost tests)

LO_STATS = S.TableStats(rows=8000.0, columns=(("k", S.ColumnStats(32.0)),))
HI_STATS = S.TableStats(rows=8000.0, columns=(("k", S.ColumnStats(7000.0)),))


def test_limit_pushdown_below_project():
    # Limit(Project(x)) -> Project(Limit(x)): truncate before wide-row work
    opt = PL.optimize(PL.Limit(PL.Project(PL.Scan(0), ("k", "d0")), 5),
                      [ORDERS], 8)
    assert isinstance(opt, PL.Project) and isinstance(opt.child, PL.Limit)
    assert opt.child.n == 5
    # chains of projects: the limit sinks below every one of them
    opt = PL.optimize(
        PL.Limit(PL.Project(PL.Project(PL.Scan(0), ("k", "d0")), ("k",)), 3),
        [ORDERS], 8)
    assert isinstance(opt, PL.Project)
    limits = find(opt, PL.Limit)
    assert limits and isinstance(limits[0].child, PL.Scan), PL.explain(opt)


def test_limit_not_pushed_below_select_or_sort():
    # Select changes row membership, Sort changes order: both pin Limit
    opt = PL.optimize(PL.Limit(PL.Select(PL.Scan(0),
                                         lambda c: c["d0"] > 0, key="p"), 5),
                      [ORDERS], 8)
    assert isinstance(opt, PL.Limit) and isinstance(opt.child, PL.Select)
    opt = PL.optimize(PL.Limit(PL.Sort(PL.Scan(0), ("k",)), 5), [ORDERS], 8)
    assert isinstance(opt, PL.Limit) and isinstance(opt.child, PL.Sort)


def test_groupby_auto_strategy_resolution():
    plan = PL.GroupBy(PL.Scan(0), ("k",), (("d0", "sum"),))
    # no stats: the documented two_phase fallback, nothing sized
    o = PL.optimize(plan, [ORDERS], 8)
    assert o.strategy == "two_phase" and not o.sized
    assert o.bucket_capacity is None
    # low key NDV: p * ndv << rows -> two_phase, bucket sized from NDV
    o = PL.optimize(plan, [ORDERS], 8, [LO_STATS])
    assert o.strategy == "two_phase" and o.sized
    assert o.bucket_capacity == S.size_bucket(32.0, 8)
    # high key NDV: partials don't dedup -> raw shuffle, bucket from rows
    o = PL.optimize(plan, [ORDERS], 8, [HI_STATS])
    assert o.strategy == "shuffle" and o.sized
    assert o.bucket_capacity == S.size_bucket(8000.0 / 8, 8)
    # an explicit strategy is never overridden
    o = PL.optimize(PL.GroupBy(PL.Scan(0), ("k",), (("d0", "sum"),),
                               strategy="shuffle"), [ORDERS], 8, [LO_STATS])
    assert o.strategy == "shuffle"
    # stats present but the KEY column was never sketched (e.g. a derived
    # aggregate column): missing information takes the two_phase
    # fallback, never worst-case shuffle
    no_key = S.TableStats(rows=8000.0, columns=(
        ("d0", S.ColumnStats(100.0)),))
    o = PL.optimize(plan, [ORDERS], 8, [no_key])
    assert o.strategy == "two_phase"
    assert o.bucket_capacity == S.size_bucket(8000.0 / 8, 8)  # rows-based


def test_cost_sizing_fills_unset_capacities_only():
    plan = PL.Join(PL.Scan(0), PL.Scan(1), ("k",))
    o = PL.optimize(plan, [ORDERS, USERS], 8, [HI_STATS, HI_STATS])
    assert o.sized and o.bucket_capacity is not None
    assert o.out_capacity is not None  # estimated match count, not c_l+c_r
    assert o.out_sized
    assert PL.plan_cost_sized(o)
    # a user-set bucket survives; the join is still out-sized
    plan_u = PL.Join(PL.Scan(0), PL.Scan(1), ("k",), bucket_capacity=999)
    o = PL.optimize(plan_u, [ORDERS, USERS], 8, [HI_STATS, HI_STATS])
    assert o.bucket_capacity == 999
    assert not o.sized and o.out_sized
    # a USER-set out_capacity is deliberate truncation, never an estimate:
    # out_sized must stay False (no truncation counting, no retry)
    plan_o = PL.Join(PL.Scan(0), PL.Scan(1), ("k",), out_capacity=50)
    o = PL.optimize(plan_o, [ORDERS, USERS], 8, [HI_STATS, HI_STATS])
    assert o.out_capacity == 50 and not o.out_sized
    assert o.sized  # only the bucket came from the estimate
    # no stats: nothing sized at all (the byte-compat guard)
    o = PL.optimize(plan, [ORDERS, USERS], 8)
    assert o.bucket_capacity is None and o.out_capacity is None
    assert not PL.plan_cost_sized(o)


def test_cost_sizing_skipped_on_single_shard():
    # p == 1: no wire to save; buckets and join outputs stay at the local
    # defaults. Only a groupby's output is sized, from its key NDV (the
    # groups the one shard can hold), and marked out_sized
    plan = PL.GroupBy(PL.Scan(0), ("k",), (("d0", "sum"),))
    o = PL.optimize(plan, [ORDERS], 1, [LO_STATS])
    assert o.strategy == "two_phase" and not o.sized
    assert o.bucket_capacity is None
    assert o.out_sized and o.out_capacity == S.with_skew_margin(32.0)
    assert o.partial_capacity is None
    jp = PL.Join(PL.Scan(0), PL.Scan(1), ("k",))
    oj = PL.optimize(jp, [ORDERS, USERS], 1, [HI_STATS, HI_STATS])
    assert not PL.plan_cost_sized(oj)


def test_groupby_sized_from_ndv_on_every_mesh():
    plan = PL.GroupBy(PL.Scan(0), ("k",), (("d0", "sum"),))
    # two-phase over 8 shards: each shard's partial holds all 32 keys at
    # most, the combined keys hash over the 8 shards
    o = PL.optimize(plan, [ORDERS], 8, [LO_STATS])
    assert o.out_sized
    assert o.partial_capacity == S.with_skew_margin(32.0)
    assert o.out_capacity == S.size_output(32.0, 8)
    # raw shuffle: only the output after the exchange
    o = PL.optimize(plan, [ORDERS], 8, [HI_STATS])
    assert o.strategy == "shuffle" and o.partial_capacity is None
    assert o.out_capacity == S.size_output(7000.0, 8) and o.out_sized
    # a capacity the user set is deliberate truncation: nothing sized
    for kw in ({"out_capacity": 9}, {"partial_capacity": 9}):
        o = PL.optimize(PL.GroupBy(PL.Scan(0), ("k",), (("d0", "sum"),),
                                   **kw), [ORDERS], 8, [LO_STATS])
        assert not o.out_sized and o.sized
        assert PL.cost_sized_stats_mask(o) == [True]  # the bucket alone
    # no stats: nothing sized
    o = PL.optimize(plan, [ORDERS], 1)
    assert o.out_capacity is None and not o.out_sized
    txt = PL.explain(PL.optimize(plan, [ORDERS], 8, [LO_STATS]),
                     [ORDERS], [LO_STATS])
    assert f"out={S.size_output(32.0, 8)}" in txt and "partial=" in txt


def test_analyzed_one_chip_groupby_reduces_in_the_kernel(ctx, monkeypatch):
    from repro.kernels import ops as kops

    routes = []
    jit = kops._segment_reduce_jit

    def spy(values, seg_ids, num_segments, op, use_kernel):
        routes.append((num_segments, use_kernel))
        return jit(values, seg_ids, num_segments, op, use_kernel)

    monkeypatch.setattr(kops, "_segment_reduce_jit", spy)
    t = ctx.analyze(ctx.scatter(int_table(5000, 6, 11)))
    frame = ctx.frame(t).groupby("k", (("d0", "sum"), ("d1", "mean")))
    plan = frame.optimized()
    assert ctx.num_shards == 1
    assert plan.out_sized and plan.out_capacity <= kops.MAX_SEGMENTS
    assert f"out={plan.out_capacity}" in frame.explain()
    frame.collect()
    assert routes and all(r == (plan.out_capacity, True) for r in routes), \
        routes


def test_cost_sizing_leaves_aligned_join_bucket_alone():
    # a range-aligned join keeps the runtime capacity-bump bucket (a whole
    # source shard may pile into one anchor range); only out is sized
    plan = PL.Join(PL.Sort(PL.Scan(0), ("k",)), PL.Scan(1), ("k",))
    o = PL.optimize(plan, [ORDERS, USERS], 8, [HI_STATS, HI_STATS])
    assert o.align == "left"
    assert o.bucket_capacity is None and not o.sized
    assert o.out_capacity is not None and o.out_sized


def test_estimator_propagates_through_operators():
    est = PL.estimate_output_stats(
        PL.Select(PL.Scan(0), lambda c: c["d0"] > 0, key="p"),
        [ORDERS], [LO_STATS])
    assert est.rows == 8000.0 * S.DEFAULT_SELECTIVITY
    est = PL.estimate_output_stats(
        PL.GroupBy(PL.Scan(0), ("k",), (("d0", "sum"),)),
        [ORDERS], [LO_STATS])
    assert est.rows == 32.0  # NDV-capped
    est = PL.estimate_output_stats(PL.Limit(PL.Scan(0), 7),
                                   [ORDERS], [LO_STATS])
    assert est.rows == 7.0
    # containment join: rows_l * rows_r / max(ndv_l, ndv_r)
    est = PL.estimate_output_stats(
        PL.Join(PL.Scan(0), PL.Scan(1), ("k",)),
        [ORDERS, USERS], [LO_STATS, HI_STATS])
    assert est.rows == pytest.approx(8000.0 * 8000.0 / 7000.0)
    # an unknown input poisons the estimate (conservative path downstream)
    assert PL.estimate_output_stats(
        PL.Join(PL.Scan(0), PL.Scan(1), ("k",)),
        [ORDERS, USERS], [LO_STATS, None]) is None


def test_explain_annotates_estimates_and_sizing():
    plan = PL.GroupBy(PL.Scan(0), ("k",), (("d0", "sum"),))
    opt = PL.optimize(plan, [ORDERS], 8, [LO_STATS])
    txt = PL.explain(opt, [ORDERS], [LO_STATS])
    assert "~rows=32" in txt and "cost-sized" in txt and "bucket=" in txt
    # without stats the old golden format is unchanged
    plain = PL.explain(PL.optimize(plan, [ORDERS], 8))
    assert "~rows" not in plain and "cost-sized" not in plain


def test_analyzed_collect_matches_eager_and_attaches_stats(ctx):
    # stats-driven planning must never change results: fused-over-analyzed
    # == eager-over-raw, bit for bit (single shard: sizing disabled, the
    # byte-compat contract; the 8-shard sizing path is covered by
    # dist_cases 'cost_groupby'/'overflow_retry' and bench_cost)
    t = int_table(300, 40, 77)
    raw = ctx.scatter(t)
    analyzed = ctx.analyze(raw)
    assert analyzed.stats is not None and analyzed.stats.rows == 300.0
    aggs = (("d0", "sum"), ("d0", "count"), ("d0", "min"))
    eager, _ = ctx.groupby(raw, "k", aggs)
    fused = ctx.frame(analyzed).groupby("k", aggs).collect()
    assert_tables_equal(eager, fused)
    assert fused.stats is not None and fused.stats.rows <= 80.0
    assert ctx.overflow_retries == 0
    # the propagated estimate feeds a SECOND hop without re-analyzing
    hop2 = ctx.frame(fused).sort("k").collect()
    assert hop2.stats is not None


def test_cost_sized_stats_mask_mirrors_executor_order(ctx):
    # the retry gate attributes each ShuffleStats entry to its node via a
    # static walk — it must line up 1:1 with what execute_plan emits
    frame = (ctx.frame(ctx.scatter(int_table(60, 10, 5)))
             .join(ctx.frame(ctx.scatter(int_table(60, 10, 6))), "k")
             .groupby("k", (("d0", "sum"),))
             .sort("k").limit(5))
    plan = frame.optimized()
    _, stats = frame.collect_with_stats()
    mask = PL.cost_sized_stats_mask(plan)
    assert len(mask) == len(stats), (len(mask), len(stats))
    assert not any(mask)  # nothing sized without stats
    # sized nodes flag exactly their own entries
    sized_join = PL.optimize(PL.Join(PL.Scan(0), PL.Scan(1), ("k",)),
                             [ORDERS, USERS], 8, [HI_STATS, HI_STATS])
    assert PL.cost_sized_stats_mask(sized_join) == [True, True]
    user_out = PL.optimize(
        PL.Join(PL.Scan(0), PL.Scan(1), ("k",), bucket_capacity=9,
                out_capacity=9), [ORDERS, USERS], 8, [HI_STATS, HI_STATS])
    assert PL.cost_sized_stats_mask(user_out) == [False, False]


def test_retry_replan_is_the_no_stats_plan():
    # what the overflow retry executes: the same logical plan re-optimized
    # WITHOUT stats — nothing sized, distinct jit cache key from the
    # sized first attempt (end-to-end retry: dist_cases 'overflow_retry')
    plan = PL.GroupBy(PL.Scan(0), ("k",), (("d0", "sum"),))
    sized = PL.optimize(plan, [ORDERS], 8, [LO_STATS])
    safe = PL.optimize(plan, [ORDERS], 8)
    assert PL.plan_cost_sized(sized) and not PL.plan_cost_sized(safe)
    assert PL.canonical_key(sized) != PL.canonical_key(safe)
    assert safe.bucket_capacity is None  # executor fallback sizing applies


def test_safe_capacity_mode_uses_unoverflowable_buckets(ctx):
    # execute_plan(safe_capacity=True) must size every unset bucket at the
    # full source capacity — the retry mode a skewed send cannot overflow
    plan = PL.Repartition(PL.Scan(0), ("k",))
    t = ctx.scatter(Table.from_arrays({"k": np.arange(64, dtype=np.int32)}))
    report: list = []

    def body(*tabs):
        return PL.execute_plan(plan, tabs, axis_name=ctx.axis_name,
                               num_shards=ctx.num_shards, report=report,
                               safe_capacity=True)

    jax.eval_shape(ctx._make_global(body), (t.columns, t.row_counts))
    assert report[0]["bucket"] == 64  # == capacity, not capacity*slack/p


# --- Table.empty N-D schemas (satellite) --------------------------------------


def test_table_empty_nd_schema():
    t = Table.empty({"k": jnp.int32,
                     "tokens": (jnp.int32, (16,)),
                     "emb": jax.ShapeDtypeStruct((4, 2), jnp.float32)},
                    capacity=8)
    assert t.columns["k"].shape == (8,)
    assert t.columns["tokens"].shape == (8, 16)
    assert t.columns["tokens"].dtype == jnp.int32
    assert t.columns["emb"].shape == (8, 4, 2)
    assert int(t.row_count) == 0


# --- window functions: golden plan shapes + fused execution -------------------


WFUNCS = (("rank", None, 0), ("cumsum", "d0", 0), ("lag", "d0", 1))


def test_window_schema_appends_result_columns():
    plan = PL.Window(PL.Scan(0), ("k",), ("d1",), WFUNCS)
    an = PL._Analysis([ORDERS])
    sch = an.schema(plan)
    assert set(sch) == {"k", "d0", "d1", "rank", "d0_cumsum", "d0_lag"}
    assert sch["rank"].dtype == I32
    assert sch["d0_cumsum"].dtype == F32  # input dtype preserved
    assert sch["d0_lag"].dtype == F32


def test_window_elides_shuffle_after_matching_sort():
    # sort on (k, d1) -> window by k order d1: exact key match, elided
    plan = PL.Window(PL.Sort(PL.Scan(0), ("k", "d1")), ("k",), ("d1",),
                     WFUNCS)
    opt = PL.optimize(plan, [ORDERS], 8)
    assert opt.skip_shuffle, PL.explain(opt)
    # sort on the PARTITION prefix alone also elides (placement is a
    # function of a prefix of the window keys)
    plan = PL.Window(PL.Sort(PL.Scan(0), ("k",)), ("k",), ("d1",), WFUNCS)
    assert PL.optimize(plan, [ORDERS], 8).skip_shuffle
    # a range-partitioned Scan (a materialized sort output) elides too
    part = RangePartitioning(("k", "d1"), 8, ("table", 3))
    plan = PL.Window(PL.Scan(0, partitioning=part), ("k",), ("d1",), WFUNCS)
    assert PL.optimize(plan, [ORDERS], 8).skip_shuffle
    # different leading key: must NOT elide
    plan = PL.Window(PL.Sort(PL.Scan(0), ("d0",)), ("k",), ("d1",), WFUNCS)
    assert not PL.optimize(plan, [ORDERS], 8).skip_shuffle


def test_window_placement_tag_elides_downstream_ops():
    # windows are row/placement-preserving: the range tag survives, so a
    # downstream groupby on the partition key elides its shuffle
    plan = PL.GroupBy(PL.Window(PL.Sort(PL.Scan(0), ("k",)), ("k",), (),
                                WFUNCS),
                      ("k",), (("d0", "sum"),))
    opt = PL.optimize(plan, [ORDERS], 8)
    assert opt.skip_shuffle, PL.explain(opt)
    gb_children = find(opt, PL.Window)
    assert gb_children and gb_children[0].skip_shuffle
    # placement on (k, d1) does NOT satisfy a groupby on k alone — a k
    # group can span shards with different d1 — so no elision there
    plan = PL.GroupBy(PL.Window(PL.Sort(PL.Scan(0), ("k", "d1")), ("k",),
                                ("d1",), WFUNCS),
                      ("k",), (("d0", "sum"),))
    assert not PL.optimize(plan, [ORDERS], 8).skip_shuffle
    # an UNSORTED window leaves its own range placement behind, which a
    # downstream sort on the same keys can reuse
    plan = PL.Sort(PL.Window(PL.Scan(0), ("k",), ("d1",), WFUNCS),
                   ("k", "d1"))
    opt = PL.optimize(plan, [ORDERS], 8)
    assert opt.skip_shuffle and not find(opt, PL.Window)[0].skip_shuffle


def test_window_projection_pushdown_keeps_func_inputs():
    # only d0_cumsum is consumed above: d1 is a window ORDER key and must
    # survive; unused payload columns below the window are dropped
    wide = {"k": jax.ShapeDtypeStruct((), I32),
            "d0": jax.ShapeDtypeStruct((), F32),
            "d1": jax.ShapeDtypeStruct((), F32),
            "junk": jax.ShapeDtypeStruct((), F32)}
    plan = PL.Project(PL.Window(PL.Scan(0), ("k",), ("d1",),
                                (("cumsum", "d0", 0),)),
                      ("k", "d0_cumsum"))
    opt = PL.optimize(plan, [wide], 8)
    projects = find(opt, PL.Project)
    below = [p for p in projects if isinstance(p.child, PL.Scan)]
    assert below and set(below[0].columns) == {"k", "d0", "d1"}, \
        PL.explain(opt)


def test_window_cost_sizing_mirrors_sort():
    plan = PL.Window(PL.Scan(0), ("k",), (), WFUNCS)
    o = PL.optimize(plan, [ORDERS], 8, [HI_STATS])
    assert o.sized
    assert o.bucket_capacity == S.size_bucket(
        8000.0 / 8, 8, factor=S.RANGE_SIZING_FACTOR)
    # row-preserving: estimates propagate unchanged through the window
    gb = PL.GroupBy(plan, ("k",), (("d0", "sum"),))
    est = PL._Estimator(PL._Analysis([ORDERS]), [HI_STATS])
    assert est.stats(gb.child).rows == 8000.0
    # elided window is never sized (no shuffle to size)
    plan = PL.Window(PL.Sort(PL.Scan(0), ("k",)), ("k",), (), WFUNCS)
    o = PL.optimize(plan, [ORDERS], 8, [HI_STATS])
    assert o.skip_shuffle and not o.sized and o.bucket_capacity is None


def test_window_canonical_key_and_stats_mask():
    mk = lambda: PL.Window(PL.Scan(0), ("k",), ("d1",), WFUNCS)
    assert PL.canonical_key(mk()) == PL.canonical_key(mk())
    assert PL.canonical_key(mk()) != PL.canonical_key(
        PL.Window(PL.Scan(0), ("k",), ("d1",), (("rank", None, 0),)))
    # one ShuffleStats entry per window, mirrored in the cost-sized mask
    plan = PL.Window(PL.Sort(PL.Scan(0), ("k",)), ("k",), (), WFUNCS)
    assert PL._stats_arity(plan) == 1
    assert len(PL.cost_sized_stats_mask(plan)) == 2  # sort + window


def test_select_not_pushed_below_window():
    # filtering before a window changes ranks/sums: the Select must stay
    # pinned above even when it only reads pass-through columns
    plan = PL.Select(PL.Window(PL.Scan(0), ("k",), ("d1",), WFUNCS),
                     lambda c: c["d0"] > 0.0, key="p")
    opt = PL.optimize(plan, [ORDERS], 8)
    assert isinstance(opt, PL.Select), PL.explain(opt)
    assert isinstance(opt.child, PL.Window)


def test_lazy_window_matches_local_oracle(ctx):
    from oracle import window_oracle
    from repro.core import ops_agg as A

    rng = np.random.default_rng(21)
    n = 400
    cols = {"k": rng.integers(0, 6, n).astype(np.int32),
            "o": rng.permutation(n).astype(np.int32),
            "d0": rng.integers(-30, 30, n).astype(np.float32)}
    funcs = ["rank", "dense_rank", "row_number", ("lag", "d0"),
             ("lead", "d0"), ("cumsum", "d0"), ("cummax", "d0"),
             ("running_mean", "d0")]
    dt = ctx.scatter(Table.from_arrays(cols))
    out = (ctx.frame(dt).window("k", funcs, order_by="o")
           .collect().to_table().to_numpy())
    want = window_oracle(cols, ["k"], ["o"], A.normalize_funcs(funcs))
    for name in want:
        np.testing.assert_array_equal(out[name], want[name], err_msg=name)
    # eager entry point: identical result, carries the range tag
    eager, _ = ctx.window(dt, "k", funcs, order_by="o")
    got = eager.to_table().to_numpy()
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    assert isinstance(eager.partitioning, RangePartitioning)
    assert eager.partitioning.keys == ("k", "o")


def test_window_explain_lists_funcs(ctx):
    dt = ctx.scatter(int_table(32, 4, seed=2))
    txt = (ctx.frame(dt).sort(["k", "d0"])
           .window("k", ["rank", ("cumsum", "d0")], order_by="d0")
           .explain())
    assert "Window(by=('k',), order_by=('d0',)" in txt
    assert "'d0_cumsum'" in txt and "shuffle=elided" in txt, txt
