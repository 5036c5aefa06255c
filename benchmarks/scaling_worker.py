"""SPMD scaling worker: one (op, workers, rows) measurement in a fresh
process (device count must be fixed before jax initializes).

Prints ``RESULT:{json}``. Invoked by bench_weak_scaling / bench_strong_-
scaling via subprocess with XLA_FLAGS=--xla_force_host_platform_device_-
count=<P>.
"""
from __future__ import annotations

import argparse
import json


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--op", required=True,
                    choices=["join_hash", "join_sort", "union"])
    ap.add_argument("--workers", type=int, required=True)
    ap.add_argument("--rows-per-worker", type=int, required=True)
    ap.add_argument("--key-range-factor", type=float, default=1.0)
    args = ap.parse_args()

    import jax

    from benchmarks.common import device_record, timeit
    from repro.core.context import DistContext
    from repro.data.synthetic import random_table

    assert jax.device_count() == args.workers, (
        jax.device_count(), args.workers)
    ctx = DistContext(axis_name="shuffle")
    p = args.workers
    n = args.rows_per_worker * p
    key_range = max(4, int(n * args.key_range_factor))
    cap = args.rows_per_worker
    a = ctx.from_local_parts([
        random_table(cap, key_range=key_range, seed=1, shard=i)
        for i in range(p)])
    b = ctx.from_local_parts([
        random_table(cap, key_range=key_range, seed=2, shard=i)
        for i in range(p)])
    bucket = max(64, int(cap * 2.0 / p))

    if args.op == "join_hash":
        fn = lambda: ctx.join(a, b, "k", algorithm="hash",
                              bucket_capacity=bucket,
                              out_capacity=4 * cap)[0].row_counts
    elif args.op == "join_sort":
        fn = lambda: ctx.join(a, b, "k", algorithm="sort",
                              bucket_capacity=bucket,
                              out_capacity=4 * cap)[0].row_counts
    else:
        fn = lambda: ctx.union(ctx.project(a, ["k"]), ctx.project(b, ["k"]),
                               bucket_capacity=bucket)[0].row_counts

    t = timeit(fn, warmup=2, iters=5)
    print("RESULT:" + json.dumps({
        **device_record(),
        "op": args.op, "workers": p, "rows_per_worker": args.rows_per_worker,
        "total_rows": n, "seconds": t,
        "rows_per_second": n / t,
    }))


if __name__ == "__main__":
    main()
