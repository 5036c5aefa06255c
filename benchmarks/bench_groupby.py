"""GroupBy strategies (arXiv:2010.14596): shuffle-then-aggregate vs
two-phase partial-aggregate -> AllToAll -> combine.

On low-cardinality keys two-phase shuffles one partial row per locally
distinct key instead of every raw row, so both the received-row count and
the dense AllToAll wire bytes (workers^2 x bucket x row_bytes) shrink by
~rows/cardinality. The table reports both, plus the measured reduction —
the hardware-independent scaling signal (the CPU container time-shares one
core, so wall-clock parity is expected; see bench_scaling's caveat).

Each (strategy, cardinality) runs in a fresh subprocess: the 8-device host
platform must be fixed before jax initializes.
"""
from __future__ import annotations

import json
import sys

from benchmarks.common import Table, run_cpu_worker

WORKERS = 8
AGGS = (("d0", "sum"), ("d0", "mean"), ("d0", "var"), ("d1", "min"),
        ("d1", "max"), ("d0", "count"))


def run_worker(strategy: str, rows_per_worker: int, key_range: int) -> dict:
    return run_cpu_worker(
        ["-m", "benchmarks.bench_groupby", "--worker",
         "--strategy", strategy, "--rows-per-worker", str(rows_per_worker),
         "--key-range", str(key_range)],
        WORKERS)


def _worker_main(argv) -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--strategy", choices=["shuffle", "two_phase"],
                    required=True)
    ap.add_argument("--rows-per-worker", type=int, required=True)
    ap.add_argument("--key-range", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmarks.common import device_record, timeit
    from repro.core.context import DistContext
    from repro.core.repartition import default_bucket_capacity
    from repro.data.synthetic import random_table
    from repro.utils import ceil_div

    assert jax.device_count() == WORKERS, jax.device_count()
    ctx = DistContext(axis_name="shuffle")
    cap, kr = args.rows_per_worker, args.key_range
    dt = ctx.from_local_parts([
        random_table(cap, key_range=kr, seed=1, shard=i)
        for i in range(WORKERS)])
    if args.strategy == "shuffle":
        # every raw row crosses the wire: bucket must absorb rows/p x skew
        bucket = default_bucket_capacity(cap, WORKERS)
    else:
        # only partial rows (<= key cardinality per shard) cross the wire
        bucket = max(8, ceil_div(kr * 2, WORKERS))

    fn = lambda: ctx.groupby(dt, "k", AGGS, strategy=args.strategy,
                             bucket_capacity=bucket)
    out, (st,) = fn()
    groups = int(out.global_rows())
    received = int(np.asarray(st.received).sum())
    overflow = int(np.asarray(st.overflow).sum())
    # bytes/row of what actually crosses the wire: raw rows for shuffle,
    # phase-1 partial rows (keys + algebraic slots) for two_phase
    if args.strategy == "shuffle":
        shipped_schema = random_table(4, key_range=4, seed=0).schema
    else:
        from repro.core import ops_agg as A
        shipped_schema = A.partial_groupby(
            random_table(4, key_range=4, seed=0), "k", AGGS).schema
    row_bytes = sum(np.dtype(v).itemsize for v in shipped_schema.values())
    # dense AllToAll: every shard ships p buckets regardless of validity
    wire_bytes = WORKERS * WORKERS * bucket * row_bytes
    secs = timeit(lambda: fn()[0].row_counts, warmup=1, iters=3)
    print("RESULT:" + json.dumps({
        **device_record(),
        "strategy": args.strategy, "rows": cap * WORKERS, "key_range": kr,
        "groups": groups, "seconds": secs, "received_rows": received,
        "overflow": overflow, "bucket": bucket, "wire_mb": wire_bytes / 1e6,
    }))


def main(quick: bool = False):
    rpw = 4_000 if quick else 40_000
    cardinalities = [64, 1024] if quick else [64, 1024, 16_384]
    t = Table(
        f"groupby strategies (P={WORKERS}, {rpw} rows/worker): "
        "two-phase shuffle-volume reduction on low-cardinality keys",
        ["key_range", "strategy", "groups", "seconds", "received_rows",
         "wire_mb", "shuffle_rows_reduction"])
    for kr in cardinalities:
        base = run_worker("shuffle", rpw, kr)
        two = run_worker("two_phase", rpw, kr)
        assert base["groups"] == two["groups"], (base, two)
        assert base["overflow"] == 0 and two["overflow"] == 0, (base, two)
        t.add(kr, "shuffle", base["groups"], base["seconds"],
              base["received_rows"], base["wire_mb"], 1.0)
        t.add(kr, "two_phase", two["groups"], two["seconds"],
              two["received_rows"], two["wire_mb"],
              base["received_rows"] / max(two["received_rows"], 1))
    t.emit()
    return t


if __name__ == "__main__":
    if "--worker" in sys.argv:
        _worker_main(sys.argv[1:])
    else:
        main("--quick" in sys.argv)
