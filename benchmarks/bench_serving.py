"""Concurrent-query serving: cold vs warm cache, sequential vs async overlap.

The paper's setting is a data-engineering layer embedded in live AI
workloads — many clients issuing small relational queries over shared
tables, where the metrics are per-query p50/p99 latency and sustained
queries/sec, not single-query wall time. This benchmark drives
``ServingSession.run_open_loop`` over an 8-shard mesh through a
mixed-shape workload (groupby / sort+limit / keyless-select+groupby /
join) in three phases:

* **cold sequential** — fresh plan cache: every shape pays its compile
  inline, and every cost-sized query pays its overflow host-sync before
  the next submission;
* **warm sequential** — same loop on the now-warm cache: 0 compiles, but
  submissions still serialize on deferred verification;
* **warm async** — bounded in-flight futures: dispatch overlaps device
  execution, and overflow verification folds into later dispatches.

Asserts — also enforced by the CI ``bench-serving`` leg — that the warm
phases run at 0 compiles and 0 recompiles, that warm-async achieves
strictly higher queries/sec than cold-sequential, and that the async
results are bit-identical per query to the sequential results.

Each measurement runs in a fresh subprocess: the 8-device host platform
must be fixed before jax initializes.
"""
from __future__ import annotations

import json
import sys

from benchmarks.common import Table, run_cpu_worker

WORKERS = 8


def run_worker(rows_per_worker: int, num_clients: int,
               queries_per_client: int) -> dict:
    return run_cpu_worker(
        ["-m", "benchmarks.bench_serving", "--worker",
         "--rows-per-worker", str(rows_per_worker),
         "--num-clients", str(num_clients),
         "--queries-per-client", str(queries_per_client)],
        WORKERS)


def _worker_main(argv) -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--rows-per-worker", type=int, required=True)
    ap.add_argument("--num-clients", type=int, required=True)
    ap.add_argument("--queries-per-client", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmarks.common import device_record
    from repro.core.context import DistContext
    from repro.core.serving import ServingSession
    from repro.core.table import Table as T
    from repro.testing.compare import tables_bitwise_equal

    assert jax.device_count() == WORKERS, jax.device_count()
    ctx = DistContext(axis_name="shuffle")
    cap = args.rows_per_worker
    n = cap * WORKERS
    rng = np.random.default_rng(42)
    orders = T.from_arrays({
        "k": rng.integers(0, 64, n).astype(np.int32),
        "d0": rng.integers(-50, 50, n).astype(np.float32),
        "d1": rng.integers(0, 1000, n).astype(np.int32)})
    dims = T.from_arrays({
        "k": np.arange(64, dtype=np.int32),
        "w": rng.integers(0, 9, 64).astype(np.float32)})

    sess = ServingSession(ctx, max_in_flight=8)
    sess.register("orders", orders, analyze=True)  # cost-sized -> deferred
    sess.register("dims", dims, analyze=True)

    # mixed plan shapes; 'sel' uses an inline keyless lambda on purpose —
    # the serving cache must keep a re-created lambda hot (content keys
    # over code + captures), or every client submission would recompile it
    workload = [
        ("gb", lambda s: s.frame("orders")
            .groupby("k", (("d0", "sum"), ("d0", "count")))),
        ("topn", lambda s: s.frame("orders").sort("k").limit(32)),
        ("sel", lambda s: s.frame("orders")
            .select(lambda c: c["d0"] > 0.0)
            .groupby("k", (("d0", "mean"),))),
        ("join", lambda s: s.frame("orders").join(s.frame("dims"), "k")
            .groupby("k", (("w", "sum"),))),
    ]

    def phase(mode):
        report, results = sess.run_open_loop(
            workload, num_clients=args.num_clients,
            queries_per_client=args.queries_per_client, mode=mode)
        print(f"# {report.summary()}", file=sys.stderr)
        return report, results

    cold, cold_res = phase("sequential")        # fresh cache: compiles
    warm_seq, seq_res = phase("sequential")     # warm: sync-per-query
    warm_async, async_res = phase("async")      # warm: overlapped dispatch

    identical = all(
        tables_bitwise_equal(a.to_table(), b.to_table())
        for a, b in zip(async_res, seq_res))
    cold_identical = all(
        tables_bitwise_equal(a.to_table(), b.to_table())
        for a, b in zip(cold_res, seq_res))

    print("RESULT:" + json.dumps({
        **device_record(),
        "rows": n, "clients": args.num_clients,
        "queries": cold.num_queries,
        "cold_sequential": cold.to_dict(),
        "warm_sequential": warm_seq.to_dict(),
        "warm_async": warm_async.to_dict(),
        "async_identical": bool(identical),
        "cold_identical": bool(cold_identical),
        "overflow_retries": ctx.overflow_retries,
    }))


def main(quick: bool = False):
    rpw = 2_000 if quick else 25_000
    clients = 4 if quick else 8
    qpc = 3 if quick else 6
    r = run_worker(rpw, num_clients=clients, queries_per_client=qpc)

    # the serving gates: never-wrong-results, never-recompile-warm,
    # and async overlap must actually buy throughput over a cold start
    assert r["async_identical"], "async results diverged from sequential"
    assert r["cold_identical"], "warm results diverged from cold"
    for ph in ("warm_sequential", "warm_async"):
        assert r[ph]["compiles"] == 0, (ph, r[ph])
        assert r[ph]["recompiles"] == 0, (ph, r[ph])
    assert r["warm_async"]["qps"] > r["cold_sequential"]["qps"], (
        r["warm_async"]["qps"], r["cold_sequential"]["qps"])

    t = Table(
        f"concurrent-query serving open loop (P={WORKERS}, "
        f"{r['rows']} rows, {r['clients']} clients x 4 shapes, "
        f"{r['queries']} queries/phase): plan-cache warmth x dispatch mode",
        ["phase", "qps", "p50_ms", "p99_ms", "compiles", "recompiles",
         "identical"])
    for ph, ident in (("cold_sequential", r["cold_identical"]),
                      ("warm_sequential", True),
                      ("warm_async", r["async_identical"])):
        d = r[ph]
        t.add(ph.replace("_", " "), round(d["qps"], 2),
              round(d["p50_ms"], 1), round(d["p99_ms"], 1),
              d["compiles"], d["recompiles"], ident)
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
                           "sections": {"serving": [table.to_dict()]}},
                          f, indent=2, default=str)
            print(f"[json] wrote {args.json}")
