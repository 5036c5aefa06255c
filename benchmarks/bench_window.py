"""Window functions: pre-sorted (boundary-carry) vs naive shuffle lowering.

Distributed window functions over an already-sorted frame need NO data
movement beyond a p-sized boundary ``all_gather`` of per-shard carry
state — the fused sort -> window chain runs the window at 0 AllToAlls and
0 wire bytes. The naive lowering (what Dask/Spark pay: repartition before
every windowed stage) range-shuffles the whole table again. The table
reports AllToAll counts, dense wire bytes, wall clock, and bit-identity
against the single-host local operator (integer-valued float payloads: no
reduction-order bit drift).

Asserts — also enforced when CI uploads the JSON — that the window step
on the pre-sorted path moves ZERO wire bytes, that the chain as a whole
ships strictly fewer bytes than the naive lowering, and that both paths
are bit-identical to the local oracle for all 8 window functions.

Each measurement runs in a fresh subprocess: the 8-device host platform
must be fixed before jax initializes.
"""
from __future__ import annotations

import json
import sys

from benchmarks.common import Table, run_cpu_worker

WORKERS = 8
FUNCS = ["rank", "dense_rank", "row_number", ("lag", "d0"), ("lead", "d0"),
         ("cumsum", "d0"), ("cummax", "d0"), ("running_mean", "d0")]


def run_worker(rows_per_worker: int, num_groups: int) -> dict:
    return run_cpu_worker(
        ["-m", "benchmarks.bench_window", "--worker",
         "--rows-per-worker", str(rows_per_worker),
         "--num-groups", str(num_groups)],
        WORKERS)


def _worker_main(argv) -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--rows-per-worker", type=int, required=True)
    ap.add_argument("--num-groups", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmarks.common import device_record, timeit
    from repro.core import ops_agg as A
    from repro.core.context import DistContext
    from repro.core.table import Table as T

    assert jax.device_count() == WORKERS, jax.device_count()
    ctx = DistContext(axis_name="shuffle")
    cap = args.rows_per_worker
    n = cap * WORKERS
    rng = np.random.default_rng(77)
    # few groups over many shards: nearly every group spans shard
    # boundaries, so the carry fold is doing real stitching; unique order
    # values keep every function deterministic -> bit-comparable
    k = rng.integers(0, args.num_groups, n).astype(np.int32)
    o = rng.permutation(n).astype(np.int32)
    d0 = rng.integers(-50, 50, n).astype(np.float32)
    parts = [T.from_arrays({"k": k[i * cap:(i + 1) * cap],
                            "o": o[i * cap:(i + 1) * cap],
                            "d0": d0[i * cap:(i + 1) * cap]})
             for i in range(WORKERS)]
    dt = ctx.from_local_parts(parts)
    bucket = 2 * cap  # skew-proof: a range bucket can absorb a whole shard

    def ov(stats):
        return sum(int(np.asarray(s.overflow).sum()) for s in stats)

    # single-host oracle: the local operator (oracle-verified in tests)
    local = A.window(T.from_arrays({"k": k, "o": o, "d0": d0}), "k", FUNCS,
                     order_by="o").to_numpy()

    # the frame both paths start from: a dist_sort output. The pre-sorted
    # lowering uses its RangePartitioning provenance (window elides to a
    # boundary all_gather); the naive lowering sees the SAME bytes with
    # the provenance stripped — what every engine without placement
    # tracking pays — and range-shuffles the whole table again.
    import dataclasses

    s, _ = ctx.sort(dt, ["k", "o"], bucket_capacity=bucket)
    s_naive = dataclasses.replace(s, partitioning=None)
    pres = ctx.frame(s).window("k", FUNCS, order_by="o")
    naive = ctx.frame(s_naive).window("k", FUNCS, order_by="o",
                                      bucket_capacity=bucket)

    nrep, prep = naive.plan_report(), pres.plan_report()
    n_out, n_stats = naive.collect_with_stats()
    p_out, p_stats = pres.collect_with_stats()
    assert ov(n_stats) == 0, f"naive overflow {ov(n_stats)}"
    assert ov(p_stats) == 0, f"pre-sorted overflow {ov(p_stats)}"

    def identical(out):
        d = out.to_table().to_numpy()
        return all(np.array_equal(d[name], local[name]) for name in local)

    win = [r for r in prep if r["op"] == "window"]
    assert len(win) == 1 and win[0]["elided"], win
    naive_ok, pres_ok = identical(n_out), identical(p_out)

    secs_naive = timeit(lambda: naive.collect().row_counts, warmup=1,
                        iters=3)
    secs_pres = timeit(lambda: pres.collect().row_counts, warmup=1,
                       iters=3)

    print("RESULT:" + json.dumps({
        **device_record(),
        "rows": n, "groups": args.num_groups,
        "naive_identical": bool(naive_ok),
        "presorted_identical": bool(pres_ok),
        "naive_alltoall": sum(not r["elided"] for r in nrep),
        "presorted_alltoall": sum(not r["elided"] for r in prep),
        "presorted_wire_mb": sum(r["wire_bytes"] for r in prep) / 1e6,
        "naive_wire_mb": sum(r["wire_bytes"] for r in nrep) / 1e6,
        "naive_seconds": secs_naive, "presorted_seconds": secs_pres,
    }))


def main(quick: bool = False):
    rpw = 2_000 if quick else 20_000
    r = run_worker(rpw, num_groups=12)
    assert r["naive_identical"] and r["presorted_identical"], r
    assert r["presorted_alltoall"] == 0, r  # boundary all_gather only
    assert r["presorted_wire_mb"] == 0.0, r
    assert r["presorted_wire_mb"] < r["naive_wire_mb"], r
    t = Table(
        f"window functions over a dist_sort output (P={WORKERS}, "
        f"{rpw} rows/worker, 8 funcs): boundary-carry elision vs the "
        "naive re-shuffle lowering",
        ["mode", "alltoall", "wire_mb", "seconds", "identical"])
    t.add("naive", r["naive_alltoall"], round(r["naive_wire_mb"], 3),
          r["naive_seconds"], r["naive_identical"])
    t.add("pre-sorted", r["presorted_alltoall"],
          round(r["presorted_wire_mb"], 3), r["presorted_seconds"],
          r["presorted_identical"])
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
                           "sections": {"window": [table.to_dict()]}},
                          f, indent=2, default=str)
            print(f"[json] wrote {args.json}")
