import os

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))

"""Distributed test cases, run in a subprocess with 8 host devices.

``python -m repro.testing.dist_cases <case>`` prints one JSON dict; the
pytest wrappers (tests/test_dist.py) assert on it. Keeping the 8-device
world in a child process leaves the main test session single-device.
"""
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro import utils as U


def _ctx(axis="shuffle"):
    from repro.core.context import DistContext
    return DistContext(axis_name=axis)


def case_join_union_sort():
    from collections import Counter

    from repro.core.table import Table
    from repro.data.synthetic import random_table, zipf_table

    ctx = _ctx()
    a = random_table(3000, key_range=300, seed=1)
    b = zipf_table(3000, key_range=300, seed=2)
    da = ctx.scatter(a, local_capacity=512)
    db = ctx.scatter(b, local_capacity=512)

    out = {}
    # join (both algorithms) vs counting oracle
    ca = Counter(np.asarray(a.columns["k"]).tolist())
    cb = Counter(np.asarray(b.columns["k"]).tolist())
    expect = sum(ca[k] * cb.get(k, 0) for k in ca)
    for algo in ("hash", "sort"):
        j, (sl, sr) = ctx.join(da, db, "k", algorithm=algo,
                               bucket_capacity=640)
        out[f"join_{algo}_rows"] = int(j.global_rows())
        out[f"join_{algo}_overflow"] = int(np.asarray(sl.overflow).sum()
                                           + np.asarray(sr.overflow).sum())
    out["join_expect"] = int(expect)

    # union vs set oracle
    u, _ = ctx.union(ctx.project(da, ["k"]), ctx.project(db, ["k"]),
                     bucket_capacity=640)
    su = set(np.asarray(a.columns["k"]).tolist()) | \
        set(np.asarray(b.columns["k"]).tolist())
    out["union_rows"] = int(u.global_rows())
    out["union_expect"] = len(su)

    # distributed sort: globally non-decreasing
    s, _ = ctx.sort(da, "k", bucket_capacity=2048)
    ks = s.to_table().to_numpy()["k"].astype(np.int64)
    out["sort_rows"] = len(ks)
    out["sort_ok"] = bool(np.all(np.diff(ks) >= 0)) and len(ks) == 3000
    return out


def case_intersect_difference():
    from repro.core.table import Table

    ctx = _ctx()
    rng = np.random.default_rng(5)
    a = Table.from_arrays({"k": rng.integers(0, 60, 400).astype(np.int32)})
    b = Table.from_arrays({"k": rng.integers(30, 90, 400).astype(np.int32)})
    da, db = ctx.scatter(a, local_capacity=128), \
        ctx.scatter(b, local_capacity=128)
    sa = set(np.asarray(a.columns["k"]).tolist())
    sb = set(np.asarray(b.columns["k"]).tolist())
    i, _ = ctx.intersect(da, db, bucket_capacity=256)
    d, _ = ctx.difference(da, db, bucket_capacity=256)
    got_i = sorted(i.to_table().to_numpy()["k"].tolist())
    got_d = sorted(d.to_table().to_numpy()["k"].tolist())
    return {"intersect_ok": got_i == sorted(sa & sb),
            "difference_ok": got_d == sorted(sa ^ sb)}


def case_groupby():
    """Both dist_groupby strategies == local groupby on the gathered table
    (itself oracle-verified in tests/test_groupby.py), and two-phase
    shuffles strictly fewer rows on low-cardinality keys."""
    from repro.core import ops_agg as A
    from repro.core.table import Table
    from repro.data.synthetic import zipf_table

    ctx = _ctx()
    key_range = 48
    parts = [zipf_table(600, key_range=key_range, seed=11, shard=i)
             for i in range(ctx.num_shards)]
    dt = ctx.from_local_parts(parts)
    aggs = (("d0", "sum"), ("d0", "count"), ("d0", "min"), ("d0", "max"),
            ("d0", "mean"), ("d0", "var"), ("d0", "first"), ("d1", "sum"))

    # reference: local groupby over the global concatenation in shard order
    cols = {k: np.concatenate([p.to_numpy()[k] for p in parts])
            for k in parts[0].column_names}
    ref_t = A.groupby(Table.from_arrays(cols), "k", aggs)
    ref = ref_t.to_numpy()

    out = {"groups_expect": int(ref_t.row_count)}
    received = {}
    for strat, cb in (("shuffle", 1024), ("two_phase", 64)):
        g, (st,) = ctx.groupby(dt, "k", aggs, strategy=strat,
                               bucket_capacity=cb)
        d = g.to_table().to_numpy()
        order = np.argsort(d["k"])
        ok = bool(np.array_equal(d["k"][order], ref["k"]))
        exact = ("d0_count",)
        for name in ref:
            got = d[name][order]
            if name in exact or not np.issubdtype(got.dtype, np.floating):
                ok &= bool(np.array_equal(got, ref[name]))
            else:
                ok &= bool(np.allclose(got, ref[name], atol=1e-4, rtol=1e-4))
        out[f"{strat}_ok"] = ok
        out[f"{strat}_overflow"] = int(np.asarray(st.overflow).sum())
        received[strat] = int(np.asarray(st.received).sum())
        out[f"{strat}_received"] = received[strat]
    out["two_phase_fewer_rows"] = received["two_phase"] < received["shuffle"]
    return out


def case_plan_fused():
    """Fused LazyFrame chain == eager op-by-op on 8 shards, with strictly
    fewer AllToAlls (pushdown + elision), including the co-partitioned
    join fast path."""
    from repro.core.table import Table

    ctx = _ctx()
    p = ctx.num_shards

    def int_table(n, kr, seed):
        rng = np.random.default_rng(seed)
        return Table.from_arrays({
            "k": rng.integers(0, kr, n).astype(np.int32),
            "d0": rng.integers(-40, 40, n).astype(np.float32),
            "d1": rng.integers(-40, 40, n).astype(np.float32)})

    cap, kr = 600, 2400  # sparse join: no truncation on either path
    orders = ctx.from_local_parts([int_table(cap, kr, 100 + i)
                                   for i in range(p)])
    users = ctx.from_local_parts([int_table(cap, kr, 200 + i)
                                  for i in range(p)])
    dims, _ = ctx.partition_by(ctx.scatter(Table.from_arrays({
        "k": np.arange(kr, dtype=np.int32),
        "dval": (np.arange(kr) % 31).astype(np.float32)})), "k")
    aggs = (("d0", "sum"), ("d0", "mean"), ("d0", "count"), ("d0_r", "max"))
    gb_bucket = 2 * cap  # eager re-shuffles are all self-sends: one bucket

    erep: list = []
    j, (sl, sr) = ctx.join(orders, users, "k", report=erep)
    s = ctx.select(j, lambda c: c["d0"] > 0.0, key="pos", report=erep)
    g, (sg,) = ctx.groupby(s, "k", aggs, strategy="shuffle",
                           bucket_capacity=gb_bucket, report=erep)
    e_out, (s3l, s3r) = ctx.join(g, dims, "k", bucket_capacity=gb_bucket,
                                 report=erep)
    eager_overflow = sum(int(np.asarray(x.overflow).sum())
                         for x in (sl, sr, sg, s3l, s3r))

    fused = (ctx.frame(orders).join(ctx.frame(users), "k")
             .select(lambda c: c["d0"] > 0.0, key="pos")
             .groupby("k", aggs, strategy="shuffle",
                      bucket_capacity=gb_bucket)
             .join(ctx.frame(dims), "k", bucket_capacity=gb_bucket))
    frep = fused.plan_report()
    f_out, f_stats = fused.collect_with_stats()
    fused_overflow = sum(int(np.asarray(x.overflow).sum()) for x in f_stats)

    from repro.testing.compare import tables_bitwise_equal
    identical = tables_bitwise_equal(e_out, f_out)
    return {
        "identical": identical,
        "rows": int(f_out.global_rows()),
        "eager_overflow": eager_overflow,
        "fused_overflow": fused_overflow,
        "eager_alltoall": sum(not r["elided"] for r in erep),
        "fused_alltoall": sum(not r["elided"] for r in frep),
        "eager_wire": sum(r["wire_bytes"] for r in erep),
        "fused_wire": sum(r["wire_bytes"] for r in frep),
    }


def case_sort_chain():
    """Range-partition provenance: fused sort->join (sort-merge) keeps the
    sorted side in place and range-aligns the other side — exactly one
    fewer AllToAll than eager, identical row multiset — and the range tag
    survives the join so a chained groupby elides its shuffle too."""
    from repro.core.table import Table

    ctx = _ctx()
    p = ctx.num_shards

    def int_table(n, kr, seed):
        rng = np.random.default_rng(seed)
        return Table.from_arrays({
            "k": rng.integers(0, kr, n).astype(np.int32),
            "d0": rng.integers(-40, 40, n).astype(np.float32)})

    cap, kr = 500, 4000  # sparse join: no truncation on either path
    orders = ctx.from_local_parts([int_table(cap, kr, 300 + i)
                                   for i in range(p)])
    users = ctx.from_local_parts([int_table(cap, kr, 400 + i)
                                  for i in range(p)])
    bucket = 2 * cap

    erep: list = []
    s_e, (st_s,) = ctx.sort(orders, "k", bucket_capacity=bucket, report=erep)
    e_out, (sl, sr) = ctx.join(s_e, users, "k", algorithm="sort",
                               bucket_capacity=bucket, report=erep)
    eager_overflow = sum(int(np.asarray(x.overflow).sum())
                         for x in (st_s, sl, sr))

    fused = (ctx.frame(orders).sort("k", bucket_capacity=bucket)
             .join(ctx.frame(users), "k", algorithm="sort",
                   bucket_capacity=bucket))
    frep = fused.plan_report()
    f_out, f_stats = fused.collect_with_stats()
    fused_overflow = sum(int(np.asarray(x.overflow).sum()) for x in f_stats)

    from repro.testing.compare import tables_bitwise_equal
    out = {
        "identical": tables_bitwise_equal(e_out, f_out),
        "rows": int(f_out.global_rows()),
        "eager_overflow": eager_overflow,
        "fused_overflow": fused_overflow,
        "eager_alltoall": sum(not r["elided"] for r in erep),
        "fused_alltoall": sum(not r["elided"] for r in frep),
    }

    # eager provenance: ctx.sort's RangePartitioning tag rides the frame()
    # boundary, so the downstream groupby elides its shuffle entirely
    gb = ctx.frame(s_e).groupby("k", (("d0", "sum"), ("d0", "count")))
    gb_rep = gb.plan_report()
    g_f = gb.collect()
    g_e, _ = ctx.groupby(s_e, "k", (("d0", "sum"), ("d0", "count")))
    out["groupby_elided"] = all(r["elided"] for r in gb_rep)
    out["groupby_identical"] = tables_bitwise_equal(g_e, g_f)
    return out


def case_sort_align_skew():
    """Regression: the range-align join must survive probe-side key skew
    with DEFAULT bucket sizing. Every probe row here targets a single
    anchor range; hash-sized buckets (~2*cap/p per destination) would
    silently drop most of them pre-join, diverging from eager."""
    from repro.core.table import Table

    ctx = _ctx()
    p = ctx.num_shards
    rng = np.random.default_rng(23)
    anchor = ctx.from_local_parts([Table.from_arrays({
        "k": rng.integers(0, 1_000_000, 400).astype(np.int32),
        "d0": rng.integers(-9, 9, 400).astype(np.float32)})
        for _ in range(p)])
    probe = ctx.from_local_parts([Table.from_arrays({
        "k": rng.integers(600_000, 600_100, 300).astype(np.int32),
        "d0": rng.integers(-9, 9, 300).astype(np.float32)})
        for _ in range(p)])

    s, _ = ctx.sort(anchor, "k")
    eager, _ = ctx.join(s, probe, "k")
    fused = ctx.frame(anchor).sort("k").join(ctx.frame(probe), "k")
    f_out, f_stats = fused.collect_with_stats()

    from repro.testing.compare import tables_bitwise_equal
    return {
        "identical": tables_bitwise_equal(eager, f_out),
        "fused_overflow": sum(int(np.asarray(x.overflow).sum())
                              for x in f_stats),
        "rows": int(f_out.global_rows()),
    }


def case_global_limit():
    """Global limit == the local oracle: head-n of the shard-order
    concatenation on unordered plans, the true top-n (bit-identical) after
    sort — never the per-shard heads."""
    from repro.core.table import Table

    ctx = _ctx()
    p = ctx.num_shards
    rng = np.random.default_rng(17)
    n_per = 200
    # unique keys: the global top-n is a unique row set, so the oracle
    # comparison is bit-exact even through the distributed sort
    keys = rng.permutation(p * n_per).astype(np.int32)
    d0 = rng.integers(-99, 99, p * n_per).astype(np.float32)
    parts = [Table.from_arrays({"k": keys[i * n_per:(i + 1) * n_per],
                                "d0": d0[i * n_per:(i + 1) * n_per]})
             for i in range(p)]
    dt = ctx.from_local_parts(parts)

    out = {"ok": True, "checked": []}
    for n in (0, 1, 7, 64, n_per + 3, p * n_per, p * n_per + 50):
        got = ctx.limit(dt, n).to_table().to_numpy()
        expect = min(n, p * n_per)
        head_ok = (len(got["k"]) == expect
                   and np.array_equal(got["k"], keys[:expect])
                   and np.array_equal(got["d0"], d0[:expect]))

        topn = (ctx.frame(dt).sort("k").limit(n).collect()
                .to_table().to_numpy())
        order = np.argsort(keys, kind="stable")
        top_ok = (np.array_equal(topn["k"], keys[order][:expect])
                  and np.array_equal(topn["d0"], d0[order][:expect]))
        out["ok"] = out["ok"] and head_ok and top_ok
        out["checked"].append([n, bool(head_ok), bool(top_ok)])

    # the limit node must be attributed in the wire accounting at 0 bytes
    rep = ctx.frame(dt).sort("k").limit(9).plan_report()
    lim = [r for r in rep if r["op"] == "limit"]
    out["limit_reported_zero"] = (len(lim) == 1
                                  and lim[0]["wire_bytes"] == 0)
    return out


def case_overflow_retry():
    """The cost model's overflow-safe contract: a skewed repartition whose
    stats-sized first-pass bucket overflows (every row shares one key, so
    one destination absorbs everything the Poisson sizing spread over p)
    must recompile ONCE at conservative capacities and still match the
    local oracle bit-for-bit — never return the truncated result."""
    from repro.core.table import Table

    ctx = _ctx()
    p = ctx.num_shards
    n_per = 400
    parts = [Table.from_arrays({
        "k": np.zeros(n_per, np.int32),  # ONE key: maximal placement skew
        "d0": np.arange(i * n_per, (i + 1) * n_per).astype(np.float32)})
        for i in range(p)]
    dt = ctx.analyze(ctx.from_local_parts(parts))
    assert dt.stats is not None and dt.stats.col("k").ndv <= 2.0

    out, (st,) = ctx.partition_by(dt, "k")
    got = out.to_table().to_numpy()
    # oracle: all rows land on hash(0)'s shard, ordered by source shard
    # then original row order == the input's global concatenation order
    want_d0 = np.concatenate([np.asarray(t.columns["d0"]) for t in parts])
    retries_first = ctx.overflow_retries
    # a failed-estimate output must carry no propagated stats (downstream
    # stages fall back to conservative sizing, no cascade)
    stats_dropped = out.stats is None
    # the same plan again: known-bad key goes STRAIGHT to the safe plan —
    # one conservative execution, no doomed sized run, no new retry
    out2, (st2,) = ctx.partition_by(dt, "k")
    got2 = out2.to_table().to_numpy()
    return {
        "retries": retries_first,
        "retries_after_repeat": ctx.overflow_retries,
        "stats_dropped": stats_dropped,
        "rows": int(out.global_rows()),
        "rows_expect": p * n_per,
        "final_overflow": int(np.asarray(st.overflow).sum()
                              + np.asarray(st2.overflow).sum()),
        "identical": bool(np.array_equal(got["d0"], want_d0)
                          and np.array_equal(got["k"],
                                             np.zeros(p * n_per, np.int32))
                          and np.array_equal(got2["d0"], want_d0)),
    }


def case_cost_groupby():
    """Cost-model strategy choice + capacity right-sizing on 8 shards:
    the optimizer must pick two_phase at low key cardinality and raw
    shuffle at high cardinality, ship strictly fewer dense wire bytes
    than the fixed-slack no-stats baseline at BOTH ends, and stay
    bit-identical to the eager result (integer-valued float payloads)."""
    from repro.core import plan as PL
    from repro.core.table import Table

    ctx = _ctx()
    p = ctx.num_shards
    rows_per = 600
    aggs = (("d0", "sum"), ("d0", "count"), ("d0", "min"))

    def run(key_range):
        parts = [Table.from_arrays({
            "k": np.random.default_rng(500 + key_range + i)
            .integers(0, key_range, rows_per).astype(np.int32),
            "d0": np.random.default_rng(900 + i)
            .integers(-40, 40, rows_per).astype(np.float32)},
            capacity=2 * rows_per)  # half-full: stats know what slack can't
            for i in range(p)]
        raw = ctx.from_local_parts(parts)
        analyzed = ctx.analyze(raw)
        base = ctx.frame(raw).groupby("k", aggs)      # no stats: fallback
        cost = ctx.frame(analyzed).groupby("k", aggs)  # stats: cost model
        strategy = cost.optimized().strategy
        base_wire = sum(r["wire_bytes"] for r in base.plan_report())
        cost_wire = sum(r["wire_bytes"] for r in cost.plan_report())
        eager, _ = ctx.groupby(raw, "k", aggs)
        got, stats = cost.collect_with_stats()
        from repro.testing.compare import tables_bitwise_equal
        return {
            "strategy": strategy,
            "base_wire": base_wire, "cost_wire": cost_wire,
            "identical": tables_bitwise_equal(eager, got),
            "overflow": sum(int(np.asarray(s.overflow).sum())
                            for s in stats),
        }

    out = {"low": run(32), "high": run(rows_per * p * 4),
           "retries": ctx.overflow_retries}
    return out


def case_window_chain():
    """Window functions over a sorted frame: the fused sort -> window ->
    select chain must run the window with ZERO AllToAlls (the sort's range
    placement satisfies it; cross-shard group carries ride a p-sized
    boundary all_gather), stay bit-identical to the single-host oracle
    for all 8 window functions, and strictly undercut the naive lowering
    (window pays its own range shuffle) on wire bytes."""
    from repro.core import ops_agg as A
    from repro.core.table import Table

    ctx = _ctx()
    p = ctx.num_shards
    rng = np.random.default_rng(31)
    n_per = 300
    n = p * n_per
    # FEW groups so nearly every group spans several shards (the carry
    # fold does real work); unique order values keep every function —
    # including cumsum/lag — deterministic, hence bit-comparable
    k = rng.integers(0, 5, n).astype(np.int32)
    o = rng.permutation(n).astype(np.int32)
    d0 = rng.integers(-30, 30, n).astype(np.float32)
    parts = [Table.from_arrays({
        "k": k[i * n_per:(i + 1) * n_per],
        "o": o[i * n_per:(i + 1) * n_per],
        "d0": d0[i * n_per:(i + 1) * n_per]}) for i in range(p)]
    dt = ctx.from_local_parts(parts)
    funcs = ["rank", "dense_rank", "row_number", ("lag", "d0"),
             ("lead", "d0"), ("cumsum", "d0"), ("cummax", "d0"),
             ("running_mean", "d0")]
    pairs = A.normalize_funcs(funcs)

    # single-host oracle (pure numpy, tests/oracle.py semantics inlined
    # via the local operator, itself oracle-verified in tests/test_window)
    local = A.window(Table.from_arrays({"k": k, "o": o, "d0": d0}), "k",
                     funcs, order_by="o").to_numpy()

    # naive lowering: the window node pays its own range partition
    naive = ctx.frame(dt).window("k", funcs, order_by="o")
    nrep = naive.plan_report()
    n_out, n_stats = naive.collect_with_stats()
    got_naive = n_out.to_table().to_numpy()

    # pre-sorted lowering: fused sort -> window -> select
    fused = (ctx.frame(dt).sort(["k", "o"]).window("k", funcs, order_by="o")
             .select(lambda c: c["rank"] <= 9, key="top9"))
    frep = fused.plan_report()
    f_out, f_stats = fused.collect_with_stats()
    got = f_out.to_table().to_numpy()

    ok = True
    for name in local:
        ok &= bool(np.array_equal(got_naive[name], local[name]))
    sel = local["rank"] <= 9
    for name in local:
        ok &= bool(np.array_equal(got[name], local[name][sel]))

    win_rep = [r for r in frep if r["op"] == "window"]
    return {
        "identical": ok,
        "rows": int(f_out.global_rows()),
        "rows_expect": int(sel.sum()),
        "naive_overflow": sum(int(np.asarray(s.overflow).sum())
                              for s in n_stats),
        "fused_overflow": sum(int(np.asarray(s.overflow).sum())
                              for s in f_stats),
        "window_elided": len(win_rep) == 1 and win_rep[0]["elided"]
        and win_rep[0]["wire_bytes"] == 0,
        "naive_window_alltoall": sum(not r["elided"] for r in nrep),
        "fused_alltoall": sum(not r["elided"] for r in frep),
        "naive_wire": sum(r["wire_bytes"] for r in nrep),
        "fused_window_wire": sum(r["wire_bytes"] for r in frep
                                 if r["op"] == "window"),
    }


def case_window_thin_shards():
    """Adversarial carry stitching: a group split across shards whose
    per-shard portions are SMALLER than the lag/lead offset (the boundary
    buffers must merge across several shards), plus an empty middle shard.
    The input is hand-tagged range-partitioned so the crafted placement is
    preserved (shuffle elided) — the carry fold sees exactly these cuts."""
    import dataclasses

    from repro.core import ops_agg as A
    from repro.core.repartition import (RangePartitioning,
                                        fresh_range_fingerprint)
    from repro.core.table import Table

    ctx = _ctx()
    p = ctx.num_shards
    sizes = [6, 1, 2, 0, 1, 6, 1, 3]
    group = [0, 0, 0, 0, 0, 0, 1, 1]  # group id per shard (contiguous)
    assert p == len(sizes), (p, len(sizes))  # the cuts are crafted for 8
    n = sum(sizes)
    cap = 8
    o_all = np.arange(n, dtype=np.int32)
    d_all = (np.arange(n, dtype=np.int32) * 3 - 7).astype(np.float32)
    k_all = np.concatenate([np.full(s, g, np.int32)
                            for s, g in zip(sizes, group)])
    parts, off = [], 0
    for i in range(p):
        s = sizes[i]
        parts.append(Table.from_arrays(
            {"k": np.pad(k_all[off:off + s], (0, cap - s)),
             "o": np.pad(o_all[off:off + s], (0, cap - s)),
             "d0": np.pad(d_all[off:off + s], (0, cap - s))},
            row_count=s))
        off += s
    dt = dataclasses.replace(
        ctx.from_local_parts(parts),
        partitioning=RangePartitioning(("k", "o"), p,
                                       fresh_range_fingerprint()))
    funcs = ["rank", "dense_rank", "row_number", ("lag", "d0", 4),
             ("lead", "d0", 4), ("cumsum", "d0"), ("cummax", "d0"),
             ("running_mean", "d0")]
    fr = ctx.frame(dt).window("k", funcs, order_by="o")
    rep = fr.plan_report()
    got = fr.collect().to_table().to_numpy()
    local = A.window(Table.from_arrays(
        {"k": k_all, "o": o_all, "d0": d_all}), "k", funcs,
        order_by="o").to_numpy()
    ok = all(bool(np.array_equal(got[name], local[name])) for name in local)
    return {"identical": ok, "rows": int(len(got["k"])), "rows_expect": n,
            "window_elided": all(r["elided"] for r in rep
                                 if r["op"] == "window")}


def case_sort_multikey():
    """Multi-key distributed sort: global lexicographic order across shards,
    row multiset preserved."""
    from repro.core.table import Table

    ctx = _ctx()
    rng = np.random.default_rng(13)
    parts = [Table.from_arrays({
        "k": rng.integers(0, 40, 700).astype(np.int32),   # heavy ties
        "d0": rng.integers(-1000, 1000, 700).astype(np.int32),
        "d1": rng.standard_normal(700).astype(np.float32)})
        for _ in range(ctx.num_shards)]
    dt = ctx.from_local_parts(parts)
    s, (st,) = ctx.sort(dt, ["k", "d0"], bucket_capacity=4096)
    d = s.to_table().to_numpy()
    pairs = list(zip(d["k"].tolist(), d["d0"].tolist()))
    in_rows = sorted(
        (int(k), int(v)) for t in parts
        for k, v in zip(t.to_numpy()["k"], t.to_numpy()["d0"]))
    return {
        "rows": len(pairs),
        "rows_expect": len(in_rows),
        "order_ok": all(x <= y for x, y in zip(pairs, pairs[1:])),
        "multiset_ok": sorted(pairs) == in_rows,
        "overflow": int(np.asarray(st.overflow).sum()),
    }


def case_moe_ep():
    """EP shard_map dispatch == single-device dispatch (same weights)."""
    from repro.models.common import ModelConfig
    from repro.models.moe import init_moe, moe_fwd
    from repro.models.common import ShardingRules

    mesh = U.make_mesh((2, 4), ("data", "model"))
    cfg = ModelConfig(arch="m", family="moe", num_layers=1, d_model=32,
                      num_heads=4, num_kv_heads=4, d_ff=0, vocab_size=64,
                      moe_num_experts=8, moe_top_k=2, moe_num_shared=1,
                      moe_d_ff=48, moe_capacity_factor=8.0)
    rules = ShardingRules(dict(mesh.shape), False)
    p, _ = init_moe(jax.random.PRNGKey(0), cfg, rules)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32), jnp.float32)

    y_local, aux_l = moe_fwd(p, x, cfg, rules, None)
    with mesh:
        y_ep, aux_ep = jax.jit(
            lambda p, x: moe_fwd(p, x, cfg, rules, mesh))(p, x)
    err = float(jnp.max(jnp.abs(y_local - y_ep)))
    # EP computes the load-balance aux per seq-shard then pmeans it — a
    # deliberate approximation of the global statistic (what distributed
    # MoEs ship). With 8 tokens/shard it is noisy: check it is a sane
    # positive value near the uniform-routing expectation (1.0).
    return {"moe_ep_err": err,
            "moe_dropped_local": float(aux_l["moe_dropped"]),
            "aux_close": 0.5 < float(aux_ep["moe_aux"]) < 3.0
            and float(aux_l["moe_aux"]) > 0}


def case_moe_decode_psum():
    """Decode-path (psum) MoE == local MoE for S == 1."""
    from repro.models.common import ModelConfig, ShardingRules
    from repro.models.moe import init_moe, moe_fwd

    mesh = U.make_mesh((2, 4), ("data", "model"))
    cfg = ModelConfig(arch="m", family="moe", num_layers=1, d_model=32,
                      num_heads=4, num_kv_heads=4, d_ff=0, vocab_size=64,
                      moe_num_experts=8, moe_top_k=2, moe_num_shared=0,
                      moe_d_ff=48, moe_capacity_factor=8.0)
    rules = ShardingRules(dict(mesh.shape), False)
    p, _ = init_moe(jax.random.PRNGKey(0), cfg, rules)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, 32), jnp.float32)
    y_local, _ = moe_fwd(p, x, cfg, rules, None)
    with mesh:
        y_ep, _ = jax.jit(lambda p, x: moe_fwd(p, x, cfg, rules, mesh))(p, x)
    return {"moe_decode_err": float(jnp.max(jnp.abs(y_local - y_ep)))}


def case_flash_decode_shard():
    """Seq-sharded flash decode == plain decode attention."""
    from repro.models import layers as NN
    from repro.models.common import ModelConfig

    mesh = U.make_mesh((2, 4), ("data", "model"))
    cfg = ModelConfig(arch="d", family="dense", num_layers=1, d_model=64,
                      num_heads=8, num_kv_heads=2, d_ff=64, vocab_size=64,
                      head_dim=8, decode_seq_shard=True)
    rng = np.random.default_rng(0)
    B, S_max = 4, 64
    cache = {"k": jnp.asarray(rng.standard_normal((B, S_max, 2, 8)),
                              jnp.float32),
             "v": jnp.asarray(rng.standard_normal((B, S_max, 2, 8)),
                              jnp.float32)}
    p, _ = NN.init_attention(jax.random.PRNGKey(0), cfg,
                             __import__("repro.models.common",
                                        fromlist=["ShardingRules"])
                             .ShardingRules(dict(mesh.shape), False))
    x = jnp.asarray(rng.standard_normal((B, 1, 64)), jnp.float32)
    pos = jnp.asarray(17, jnp.int32)
    sin_cos = NN.rope_tables(jnp.arange(1) + 17, cfg.hd, 1e4)
    with mesh:
        y_shard, _ = jax.jit(lambda p, x, c: NN.attention_fwd(
            p, x, cfg, mode="decode", rope=sin_cos, cache=c, pos=pos,
            mesh=mesh))(p, x, cache)
    y_plain, _ = NN.attention_fwd(p, x, cfg, mode="decode", rope=sin_cos,
                                  cache=cache, pos=pos, mesh=None)
    return {"flash_decode_err": float(jnp.max(jnp.abs(y_shard - y_plain)))}


def case_compress_pod():
    """int8 error-feedback pod gradients: quantized mean close to exact,
    error feedback reduces bias across steps."""
    from repro.models.common import ModelConfig
    from repro.models.factory import build_model
    from repro.train.optimizer import OptConfig
    from repro.train.steps import (init_train_state, make_train_step,
                                   train_state_specs)

    mesh = U.make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = ModelConfig(arch="t", family="dense", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=128,
                      head_dim=8, remat="none")
    model = build_model(cfg, mesh)
    ocfg = OptConfig(lr=1e-2, warmup_steps=2, total_steps=20)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(1, 128, (8, 16)), jnp.int32),
             "weight": jnp.ones((8,), jnp.float32)}
    with mesh:
        st_c = init_train_state(model, jax.random.PRNGKey(0),
                                compress_pod=True, n_pods=2)
        step_c = jax.jit(make_train_step(model, ocfg, compress_pod=True))
        st_e = init_train_state(model, jax.random.PRNGKey(0))
        step_e = jax.jit(make_train_step(model, ocfg))
        for i in range(3):
            st_c, mc = step_c(st_c, batch)
            st_e, me = step_e(st_e, batch)
    # compressed training should track exact training closely
    diffs = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32))))
             for a, b in zip(jax.tree.leaves(st_c.params),
                             jax.tree.leaves(st_e.params))]
    return {"pod_compress_max_param_diff": max(diffs),
            "loss_close": abs(float(mc["loss"]) - float(me["loss"])) < 0.2}


def case_elastic_restore():
    """Save on a (4,2) mesh, restore on (2,4) and (8,) — loss identical."""
    import tempfile

    from repro.models.common import ModelConfig
    from repro.models.factory import build_model
    from repro.train import checkpoint as ckpt
    from repro.train.steps import init_train_state, train_state_specs

    cfg = ModelConfig(arch="t", family="dense", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=128,
                      head_dim=8, remat="none")
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(1, 128, (8, 16)), jnp.int32),
             "weight": jnp.ones((8,), jnp.float32)}

    losses = {}
    d = tempfile.mkdtemp()
    state0 = None
    for name, shape, axes in [("a", (4, 2), ("data", "model")),
                              ("b", (2, 4), ("data", "model")),
                              ("c", (8, 1), ("data", "model"))]:
        mesh = U.make_mesh(shape, axes)
        model = build_model(cfg, mesh)
        with mesh:
            if state0 is None:
                state = init_train_state(model, jax.random.PRNGKey(0))
                ckpt.save(d, 1, state)
                state0 = True
            from repro.train.steps import train_state_specs as tss
            like = jax.eval_shape(
                lambda k: init_train_state(model, k), jax.random.PRNGKey(0))
            state, step = ckpt.CheckpointManager(d).resume(
                like, mesh=mesh, specs=tss(model))
            loss, _ = jax.jit(model.loss_fn)(state.params, batch)
        losses[name] = float(loss)
    vals = list(losses.values())
    # different mesh shapes change bf16 reduction order: allow ~1e-3
    return {"elastic_losses": vals,
            "elastic_ok": max(vals) - min(vals) < 2e-3}


def case_serving_async():
    """Concurrent-query serving on 8 shards: N interleaved clients driving
    collect_async through a shared ServingSession must produce per-query
    results bit-identical to sequential collects, with ZERO compiles on
    the warm cache (including the inline keyless lambda — code-identity
    keys keep a re-created predicate hot), and out-of-order future
    resolution must not perturb anything."""
    from repro.core.serving import ServingSession
    from repro.core.table import Table
    from repro.testing.compare import tables_bitwise_equal

    ctx = _ctx()
    p = ctx.num_shards
    rng = np.random.default_rng(71)
    n = 500 * p
    orders = Table.from_arrays({
        "k": rng.integers(0, 64, n).astype(np.int32),
        "d0": rng.integers(-50, 50, n).astype(np.float32)})
    dims = Table.from_arrays({
        "k": np.arange(64, dtype=np.int32),
        "w": rng.integers(0, 9, 64).astype(np.float32)})
    sess = ServingSession(ctx, max_in_flight=6)
    sess.register("orders", orders, analyze=True)
    sess.register("dims", dims, analyze=True)
    workload = [
        ("gb", lambda s: s.frame("orders")
            .groupby("k", (("d0", "sum"), ("d0", "count")))),
        ("topn", lambda s: s.frame("orders").sort("k").limit(16)),
        ("sel", lambda s: s.frame("orders")
            .select(lambda c: c["d0"] > 0.0)
            .groupby("k", (("d0", "mean"),))),
        ("join", lambda s: s.frame("orders").join(s.frame("dims"), "k")
            .groupby("k", (("w", "sum"),))),
    ]
    seq_rep, seq_res = sess.run_open_loop(
        workload, num_clients=3, queries_per_client=2, mode="sequential")
    asy_rep, asy_res = sess.run_open_loop(
        workload, num_clients=3, queries_per_client=2, mode="async")
    identical = all(tables_bitwise_equal(a.to_table(), b.to_table())
                    for a, b in zip(asy_res, seq_res))

    # out-of-order resolution: submit every shape, resolve in REVERSE
    pre = ctx.cache_stats()
    base = [sess.submit(b).result() for _, b in workload]
    futs = [sess.submit(b) for _, b in workload]
    rev = [f.result() for f in reversed(futs)][::-1]
    rev_ok = all(tables_bitwise_equal(a.to_table(), b.to_table())
                 for a, b in zip(rev, base))
    return {
        "identical": identical,
        "reverse_resolution_ok": rev_ok,
        "cold_compiles": seq_rep.compiles,
        "warm_compiles": asy_rep.compiles + (
            ctx.cache_stats()["misses"] - pre["misses"]),
        "warm_recompiles": asy_rep.recompiles,
        "queries_per_mode": seq_rep.num_queries,
        "seq_qps": seq_rep.qps, "async_qps": asy_rep.qps,
        "p50_ms": asy_rep.p50_ms, "p99_ms": asy_rep.p99_ms,
    }


def case_async_overflow_deferred():
    """The deferred-verification contract on the async path: a cost-sized
    plan with a WRONG estimate (single-key skew, same setup as
    case_overflow_retry) dispatches with no host sync — the overflow is
    only discovered at ``future.result()``, which runs EXACTLY ONE
    safe-capacity retry and returns oracle-exact rows. A repeat submit of
    the known-bad plan goes straight to the safe executable (no new
    retry), and both the sized and safe executables sit in the plan cache
    under distinct key namespaces."""
    from repro.core.table import Table

    ctx = _ctx()
    p = ctx.num_shards
    n_per = 400
    parts = [Table.from_arrays({
        "k": np.zeros(n_per, np.int32),  # ONE key: maximal placement skew
        "d0": np.arange(i * n_per, (i + 1) * n_per).astype(np.float32)})
        for i in range(p)]
    dt = ctx.analyze(ctx.from_local_parts(parts))
    assert dt.stats is not None and dt.stats.col("k").ndv <= 2.0

    fut = ctx.frame(dt).partition_by("k").collect_async()
    # dispatch must NOT have verified anything: the wrong estimate is
    # still unknown to the host, the future unresolved
    deferred = (ctx.overflow_retries == 0) and not fut.done
    out = fut.result()  # <- verification: discovers overflow, retries safe
    got = out.to_table().to_numpy()
    want_d0 = np.concatenate([np.asarray(t.columns["d0"]) for t in parts])
    retries_first = ctx.overflow_retries
    again = fut.result()  # resolved future: same object, no re-execution
    idempotent = again is out

    # repeat submit: the known-bad key routes straight to the safe plan
    out2 = ctx.frame(dt).partition_by("k").collect_async().result()
    got2 = out2.to_table().to_numpy()
    namespaces = sorted({k[0][0] for k in ctx.plan_cache.keys()})
    return {
        "deferred": deferred,
        "retries": retries_first,
        "retries_after_repeat": ctx.overflow_retries,
        "idempotent": idempotent,
        "stats_dropped": out.stats is None,
        "rows": int(out.global_rows()),
        "rows_expect": p * n_per,
        "identical": bool(
            np.array_equal(got["d0"], want_d0)
            and np.array_equal(got["k"], np.zeros(p * n_per, np.int32))
            and np.array_equal(got2["d0"], want_d0)),
        "cache_namespaces": namespaces,
    }


def case_staged_shuffle():
    """Staged / ring shuffles vs the monolithic exchange, under skew.

    Bit-identity is the whole contract: identical rows (sorted-multiset
    bit compare), identical overflow with an undersized bucket, identical
    wire-byte accounting in the report — only the collective decomposition
    differs. Also regression-covers the empty-table edge (capacity-0
    shards through a staged shuffle).
    """
    from repro.core.table import Table
    from repro.testing.compare import tables_bitwise_equal

    ctx = _ctx()
    p = ctx.num_shards
    rng = np.random.default_rng(11)
    n_per = 300
    # heavy skew: ~half the rows share one key -> one destination bucket
    # overflows at bucket_capacity=64 (300 rows/shard, ~150 to one shard)
    k = np.where(rng.random(p * n_per) < 0.5, 0,
                 rng.integers(0, 997, p * n_per)).astype(np.int32)
    host = Table.from_arrays({"k": k,
                              "v": rng.random(p * n_per).astype(np.float32)})
    dt = ctx.scatter(host, local_capacity=n_per)

    results, reports = {}, {}
    for name, kw in (("mono", dict(stages=1)),
                     ("staged", dict(stages=3)),
                     ("ring", dict(shuffle_mode="ring"))):
        rep = []
        out, (st,) = ctx.partition_by(dt, "k", bucket_capacity=64,
                                      report=rep, **kw)
        results[name] = (out, int(np.asarray(st.overflow).sum()),
                         int(out.global_rows()))
        reports[name] = rep[0]

    mono, staged, ring = (results[n] for n in ("mono", "staged", "ring"))
    # empty table (capacity-0 shards) through a staged shuffle: the
    # pack_by_partition n==0 guard and the c==0 gather guard
    empty = ctx.from_local_parts(
        [Table.empty({"k": jnp.int32}, 0)] * p)
    eout, (est_,) = ctx.partition_by(empty, "k", bucket_capacity=4, stages=2)

    return {
        "overflow_mono": mono[1],
        "overflow_positive": mono[1] > 0,
        "overflow_identical": mono[1] == staged[1] == ring[1],
        "rows_identical": mono[2] == staged[2] == ring[2],
        "staged_bitwise_equal": tables_bitwise_equal(mono[0], staged[0]),
        "ring_bitwise_equal": tables_bitwise_equal(mono[0], ring[0]),
        "wire_bytes_identical": len({reports[n]["wire_bytes"]
                                     for n in reports}) == 1,
        "stages_reported": [reports[n]["stages"]
                            for n in ("mono", "staged", "ring")],
        "modes_reported": [reports[n]["mode"]
                           for n in ("mono", "staged", "ring")],
        "empty_rows": int(eout.global_rows()),
        "empty_overflow": int(np.asarray(est_.overflow).sum()),
    }


def case_verify_audit():
    """``verify.audit_collectives`` on 8 shards: the static per-record
    accounting derived from ``plan_report`` must equal the collective
    counts in the actually-traced fused jaxpr, across every distributed
    operator family — hash-shuffled groupby chain, sort->join range
    alignment (sort-merge fast path), sort->window boundary carries,
    staged and ring explicit repartitions, and a global limit."""
    from repro.core import verify as V
    from repro.core.table import Table

    ctx = _ctx()
    p = ctx.num_shards

    def int_table(n, kr, seed):
        rng = np.random.default_rng(seed)
        return Table.from_arrays({
            "k": rng.integers(0, kr, n).astype(np.int32),
            "d0": rng.integers(-40, 40, n).astype(np.float32),
            "d1": rng.integers(-40, 40, n).astype(np.float32)})

    cap, kr = 200, 800
    orders = ctx.from_local_parts([int_table(cap, kr, 500 + i)
                                   for i in range(p)])
    users = ctx.from_local_parts([int_table(cap, kr, 600 + i)
                                  for i in range(p)])
    bucket = 2 * cap

    pipelines = {
        "groupby_chain": (
            ctx.frame(orders).join(ctx.frame(users), "k",
                                   bucket_capacity=bucket,
                                   out_capacity=4 * cap)
            .select(lambda c: c["d0"] > 0.0, key="pos")
            .groupby("k", (("d0", "sum"), ("d0", "count")),
                     strategy="shuffle", bucket_capacity=bucket)),
        "sort_join_align": (
            ctx.frame(orders).sort("k", bucket_capacity=bucket)
            .join(ctx.frame(users), "k", algorithm="sort",
                  bucket_capacity=bucket, out_capacity=4 * cap)),
        "sort_window": (
            ctx.frame(orders).sort(("k", "d1"), bucket_capacity=bucket)
            .window(("k",), (("rank", None, 0), ("cumsum", "d0", 0)),
                    order_by=("d1",), bucket_capacity=bucket)),
        "staged_shuffle": (
            ctx.frame(orders).partition_by("k", bucket_capacity=bucket,
                                           stages=3)),
        "ring_shuffle": (
            ctx.frame(orders).partition_by("k", bucket_capacity=bucket,
                                           shuffle_mode="ring")),
        "sorted_limit": (
            ctx.frame(orders).sort("k", bucket_capacity=bucket).limit(17)),
    }

    out = {}
    for name, fr in pipelines.items():
        audit = V.audit_collectives(fr, strict=False)
        out[name] = {"matched": audit["matched"],
                     "expected": audit["expected"],
                     "actual": audit["actual"]}
    out["all_matched"] = all(v["matched"] for v in out.values())
    return out


def case_exchange_scopes():
    """The join's hash exchange is named in the compiled program: every
    AllToAll, the pack's sort and its histogram kernel sit under
    ``engine.exchange`` inside ``engine.join``."""
    import re

    from repro.data.synthetic import random_table

    ctx = _ctx()
    da = ctx.scatter(random_table(3000, key_range=300, seed=1),
                     local_capacity=512)
    db = ctx.scatter(random_table(3000, key_range=300, seed=2),
                     local_capacity=512)
    fr = ctx.frame(da).join(ctx.frame(db), on="k")
    fr.collect()
    args = tuple((t.columns, t.row_counts) for t in fr._inputs)
    (key,) = ctx.plan_cache.keys()
    text = ctx.plan_cache.get(key).lower(*args).compile().as_text()
    named = {"all_to_all": [], "pack_sort": [], "histogram": []}
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if not m:
            continue
        op_name = m.group(1)
        scopes = re.findall(r"engine\.[a-z]+(?=/|$)", op_name)
        if " all-to-all(" in line:
            named["all_to_all"].append(scopes)
        elif " sort(" in line and "engine.exchange" in op_name:
            named["pack_sort"].append(scopes)
        elif "bucket_histogram" in op_name:
            named["histogram"].append(scopes)
    return {k: {"count": len(v),
                "under_join_exchange": all(
                    s[-2:] == ["engine.join", "engine.exchange"] for s in v)}
            for k, v in named.items()}


def case_overflow_retry_spans():
    """The late safe-capacity retry of case_overflow_retry's skewed
    repartition, traced: one ``engine.retry`` span with its rung, inside
    the ``engine.verify`` of the same query, holding that query's second
    compile."""
    import tempfile

    from repro.core.table import Table
    from repro.testing.spans import engine_spans

    ctx = _ctx()
    p = ctx.num_shards
    parts = [Table.from_arrays({
        "k": np.zeros(400, np.int32),
        "d0": np.arange(i * 400, (i + 1) * 400).astype(np.float32)})
        for i in range(p)]
    dt = ctx.analyze(ctx.from_local_parts(parts))
    trace_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(trace_dir)
    try:
        ctx.frame(dt).partition_by("k").collect_async().result()
    finally:
        jax.profiler.stop_trace()
    spans = engine_spans(trace_dir)
    by = {}
    for name, s, e, st in spans:
        by.setdefault(name, []).append((s, e, st))
    (retry,) = by.get("engine.retry", [None])
    (verify,) = by.get("engine.verify", [None])
    compiles = by.get("engine.compile", [])
    return {
        "retries": ctx.overflow_retries,
        "retry_spans": len(by.get("engine.retry", [])),
        "rung": retry[2].get("rung") if retry else None,
        "one_query": len({st.get("query") for _, _, _, st in spans}) == 1,
        "inside_verify": bool(retry and verify and verify[0] <= retry[0]
                              and retry[1] <= verify[1]),
        "compile_namespaces": sorted(c[2]["cache"] for c in compiles),
        "retry_compiles": sum(retry[0] <= c[0] and c[1] <= retry[1]
                              for c in compiles) if retry else 0,
    }


CASES = {k[5:]: v for k, v in list(globals().items())
         if k.startswith("case_")}


def main():
    case = sys.argv[1]
    out = CASES[case]()
    print("JSON:" + json.dumps(out))


if __name__ == "__main__":
    main()
