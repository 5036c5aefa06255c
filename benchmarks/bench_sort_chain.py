"""Sort->join chains: range-partition provenance vs eager re-shuffling.

``dist_sort`` pays an AllToAll to range-partition its input; eager
execution then throws that placement away and the following sort-merge
join hash-shuffles BOTH sides again (3 AllToAlls for the chain). The plan
optimizer instead tracks the sort's ``RangePartitioning`` tag, keeps the
sorted side in place, and range-ALIGNS the other side to its boundaries
(re-derived from per-shard key maxima — an all_gather of p scalars, not a
shuffle): 2 AllToAlls, bit-identical output. The chained groupby on the
same key then elides its shuffle entirely off the surviving tag.

The table reports AllToAll counts, dense wire bytes, wall clock, and the
row-multiset equality check (integer-valued float payloads: no reduction-
order bit drift). Asserts — also enforced when CI uploads the JSON — that
the fused chain runs STRICTLY fewer AllToAlls and is bit-identical.

Each measurement runs in a fresh subprocess: the 8-device host platform
must be fixed before jax initializes.
"""
from __future__ import annotations

import json
import sys

from benchmarks.common import Table, run_cpu_worker

WORKERS = 8
AGGS = (("d0", "sum"), ("d0", "count"), ("d0_r", "max"))


def run_worker(rows_per_worker: int, key_range: int) -> dict:
    return run_cpu_worker(
        ["-m", "benchmarks.bench_sort_chain", "--worker",
         "--rows-per-worker", str(rows_per_worker),
         "--key-range", str(key_range)],
        WORKERS)


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
    from repro.core.table import Table as T

    assert jax.device_count() == WORKERS, jax.device_count()
    ctx = DistContext(axis_name="shuffle")
    cap, kr = args.rows_per_worker, args.key_range

    def int_table(rows, seed):
        """Integer-valued float payloads: exact in f32, bit-comparable."""
        rng = np.random.default_rng(seed)
        return T.from_arrays({
            "k": rng.integers(0, kr, rows).astype(np.int32),
            "d0": rng.integers(-50, 50, rows).astype(np.float32)})

    orders = ctx.from_local_parts(
        [int_table(cap, seed=100 + i) for i in range(WORKERS)])
    users = ctx.from_local_parts(
        [int_table(cap, seed=200 + i) for i in range(WORKERS)])
    # skew-proof buckets: a range bucket can absorb a whole shard's rows
    bucket = 2 * cap

    def ov(stats):
        return sum(int(np.asarray(s.overflow).sum()) for s in stats)

    def eager_chain(report=None, overflow=None):
        s, st1 = ctx.sort(orders, "k", bucket_capacity=bucket, report=report)
        j, st2 = ctx.join(s, users, "k", algorithm="sort",
                          bucket_capacity=bucket, report=report)
        g, st3 = ctx.groupby(j, "k", AGGS, strategy="shuffle",
                             bucket_capacity=2 * bucket, report=report)
        if overflow is not None:
            overflow.append(ov(st1) + ov(st2) + ov(st3))
        return g

    fused = (ctx.frame(orders).sort("k", bucket_capacity=bucket)
             .join(ctx.frame(users), "k", algorithm="sort",
                   bucket_capacity=bucket)
             .groupby("k", AGGS, strategy="shuffle",
                      bucket_capacity=2 * bucket))

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

    secs_eager = timeit(lambda: eager_chain().row_counts, warmup=1, iters=3)
    secs_fused = timeit(lambda: fused.collect().row_counts, warmup=1, iters=3)

    print("RESULT:" + json.dumps({
        **device_record(),
        "rows": cap * WORKERS, "key_range": kr,
        "groups": int(np.asarray(f_out.global_rows())),
        "identical": bool(identical),
        "eager_alltoall": eager_a2a, "fused_alltoall": fused_a2a,
        "eager_wire_mb": eager_wire / 1e6, "fused_wire_mb": fused_wire / 1e6,
        "eager_seconds": secs_eager, "fused_seconds": secs_fused,
    }))


def main(quick: bool = False):
    rpw = 2_000 if quick else 20_000
    # sparse join (matches ~= rows^2/key_range stay inside out_capacity):
    # neither path truncates, so bit-identity is a hard assert
    key_range = rpw * 4
    t = Table(
        f"sort->join->groupby chain (P={WORKERS}, {rpw} rows/worker): "
        "range-partition provenance keeps the sorted side in place and "
        "elides downstream shuffles vs eager re-shuffling",
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
                           "sections": {"sort_chain": [table.to_dict()]}},
                          f, indent=2, default=str)
            print(f"[json] wrote {args.json}")
