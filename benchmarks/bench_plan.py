"""Fused LazyFrame plans vs eager op-by-op execution (the plan-layer win).

The ETL chain measured (paper Fig. 3 composition + the arXiv:2209.06146
operator algebra):

    join(orders, users, on=k) -> select(d0 > 0) -> groupby(k, aggs)
        -> join(dims, on=k)                       # dims pre-partitioned on k

Eager: 4 dispatches, 6 potential AllToAlls (join 2 + groupby 1 + join 2,
the pre-partitioning itself excluded), full-width rows on the wire.
Fused: ONE shard_map program; the optimizer pushes the filter and the
column projections below the first join's shuffles, elides the groupby
shuffle (join output is already hash-partitioned on k) and both shuffles
of the second join (co-partitioned fast path). The table reports AllToAll
counts, dense wire bytes (workers^2 x bucket x row_bytes — what the
collective actually ships), received rows, wall clock, and a bit-identical
equality check of fused vs eager results (payloads are integer-valued
floats, so aggregation order cannot perturb bits).

Each measurement runs in a fresh subprocess: the 8-device host platform
must be fixed before jax initializes.
"""
from __future__ import annotations

import json
import sys

from benchmarks.common import Table, run_cpu_worker

WORKERS = 8
AGGS = (("d0", "sum"), ("d0", "mean"), ("d0", "var"), ("d0", "count"),
        ("d0_r", "min"), ("d0_r", "max"))


def run_worker(rows_per_worker: int, key_range: int) -> dict:
    return run_cpu_worker(
        ["-m", "benchmarks.bench_plan", "--worker",
         "--rows-per-worker", str(rows_per_worker),
         "--key-range", str(key_range)],
        WORKERS)


def _int_table(rows: int, key_range: int, payloads: int, seed: int,
               key_name: str = "k"):
    """Integer-valued float payloads: sums are exact in f32, so fused and
    eager results can be compared bit-for-bit."""
    import numpy as np

    from repro.core.table import Table as T

    rng = np.random.default_rng(seed)
    cols = {key_name: rng.integers(0, key_range, rows).astype(np.int32)}
    for i in range(payloads):
        cols[f"d{i}"] = rng.integers(-50, 50, rows).astype(np.float32)
    return T.from_arrays(cols)


def _worker_main(argv) -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--rows-per-worker", type=int, required=True)
    ap.add_argument("--key-range", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmarks.common import device_record, timeit
    from repro.core.context import DistContext

    assert jax.device_count() == WORKERS, jax.device_count()
    ctx = DistContext(axis_name="shuffle")
    cap, kr = args.rows_per_worker, args.key_range
    pred_key = "d0_positive"

    orders = ctx.from_local_parts(
        [_int_table(cap, kr, 3, seed=100 + i) for i in range(WORKERS)])
    users = ctx.from_local_parts(
        [_int_table(cap, kr, 3, seed=200 + i) for i in range(WORKERS)])
    # dims: unique keys, pre-partitioned on k once (outside the timed chain)
    from repro.core.table import Table as T
    dims_host = T.from_arrays({
        "k": np.arange(kr, dtype=np.int32),
        "dval": (np.arange(kr) % 97).astype(np.float32)})
    dims, _ = ctx.partition_by(ctx.scatter(dims_host), "k", seed=7)

    # The eager groupby/join-2 inputs arrive pre-concentrated (the first
    # join already placed each key on its hash shard, so the re-shuffle is
    # all self-sends into ONE bucket): their buckets must absorb a whole
    # shard's rows, not rows/P. The fused plan elides those shuffles, so
    # its buckets are irrelevant — but the node params stay identical to
    # keep the programs comparable op-for-op.
    gb_bucket = 2 * cap

    def ov(stats):
        return sum(int(np.asarray(s.overflow).sum()) for s in stats)

    def eager_chain(report=None, overflow=None):
        j, st1 = ctx.join(orders, users, "k", report=report)
        s = ctx.select(j, lambda c: c["d0"] > 0.0, key=pred_key,
                       report=report)
        g, st2 = ctx.groupby(s, "k", AGGS, strategy="shuffle",
                             bucket_capacity=gb_bucket, report=report)
        out, st3 = ctx.join(g, dims, "k", bucket_capacity=gb_bucket,
                            report=report)
        if overflow is not None:
            overflow.append(ov(st1) + ov(st2) + ov(st3))
        return out

    fused = (ctx.frame(orders)
             .join(ctx.frame(users), "k")
             .select(lambda c: c["d0"] > 0.0, key=pred_key)
             .groupby("k", AGGS, strategy="shuffle",
                      bucket_capacity=gb_bucket)
             .join(ctx.frame(dims), "k", bucket_capacity=gb_bucket))

    # static shuffle accounting: fused from the optimizer's dry run, eager
    # from the per-op trace reports (fresh context -> every op traces once)
    eager_report: list = []
    eager_overflow: list = []
    e_out = eager_chain(report=eager_report, overflow=eager_overflow)
    f_report = fused.plan_report()
    f_out, f_stats = fused.collect_with_stats()
    assert eager_overflow[0] == 0, f"eager overflow {eager_overflow[0]}"
    assert ov(f_stats) == 0, f"fused overflow {ov(f_stats)}"

    def acct(report):
        return (sum(not r["elided"] for r in report),
                sum(r["wire_bytes"] for r in report))

    eager_a2a, eager_wire = acct(eager_report)
    fused_a2a, fused_wire = acct(f_report)

    from repro.testing.compare import tables_bitwise_equal
    identical = tables_bitwise_equal(e_out, f_out)
    received = sum(int(np.asarray(s.received).sum()) for s in f_stats)

    secs_eager = timeit(lambda: eager_chain().row_counts, warmup=1, iters=3)
    secs_fused = timeit(lambda: fused.collect().row_counts, warmup=1, iters=3)

    print("RESULT:" + json.dumps({
        **device_record(),
        "rows": cap * WORKERS, "key_range": kr,
        "groups": int(np.asarray(f_out.global_rows())),
        "identical": bool(identical),
        "eager_alltoall": eager_a2a, "fused_alltoall": fused_a2a,
        "eager_wire_mb": eager_wire / 1e6, "fused_wire_mb": fused_wire / 1e6,
        "fused_received_rows": received,
        "eager_seconds": secs_eager, "fused_seconds": secs_fused,
    }))


def main(quick: bool = False):
    rpw = 2_000 if quick else 20_000
    # sparse join: expected matches (= rows^2/key_range) stay well inside
    # the default join out_capacity, so neither path hits the truncation
    # failure mode and results must agree bit-for-bit
    key_range = rpw * 4
    t = Table(
        f"lazy plan fusion (P={WORKERS}, {rpw} rows/worker): one shard_map "
        "program per pipeline — pushdown + shuffle elision vs eager op-by-op",
        ["mode", "alltoall", "wire_mb", "seconds", "groups", "identical",
         "wire_reduction"])
    r = run_worker(rpw, key_range)
    assert r["identical"], "fused result != eager result"
    assert r["fused_alltoall"] < r["eager_alltoall"], r
    assert r["fused_wire_mb"] < r["eager_wire_mb"], r
    t.add("eager", r["eager_alltoall"], round(r["eager_wire_mb"], 3),
          r["eager_seconds"], r["groups"], r["identical"], 1.0)
    t.add("fused", r["fused_alltoall"], round(r["fused_wire_mb"], 3),
          r["fused_seconds"], r["groups"], r["identical"],
          round(r["eager_wire_mb"] / max(r["fused_wire_mb"], 1e-9), 1))
    t.emit()
    return t


if __name__ == "__main__":
    if "--worker" in sys.argv:
        _worker_main([a for a in sys.argv[1:] if a != "--json"])
    else:
        import argparse

        ap = argparse.ArgumentParser(description=__doc__)
        ap.add_argument("--quick", action="store_true")
        ap.add_argument("--json", metavar="PATH", default=None)
        args = ap.parse_args()
        table = main(args.quick)
        if args.json:
            with open(args.json, "w") as f:
                json.dump({"quick": args.quick,
                           "sections": {"plan": [table.to_dict()]}},
                          f, indent=2, default=str)
            print(f"[json] wrote {args.json}")
