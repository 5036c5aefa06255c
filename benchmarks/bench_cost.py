"""Cost-model-driven physical planning vs the fixed-slack baseline.

Sweeps key cardinality over the same groupby pipeline twice per point:
once over a raw (no-stats) table — the optimizer falls back to the
documented ``two_phase`` strategy and the ``FALLBACK_SLACK`` capacity
heuristic — and once over the SAME table after ``ctx.analyze`` (one
vectorized stats pass: row counts + per-key NDV sketch). With stats the
optimizer picks the strategy per node from the arXiv:2010.14596
crossover (``two_phase`` while ``shards * NDV < rows``, raw ``shuffle``
above it) and right-sizes the AllToAll bucket from estimated occupancy
instead of table capacity.

Asserted at BOTH sweep ends (also under CI's --quick smoke):
  * the model picks the cheaper strategy (two_phase low, shuffle high);
  * the cost-sized plan ships strictly fewer dense wire bytes
    (workers^2 x bucket x row_bytes) than the fixed-slack baseline;
  * results are bit-identical to the eager oracle (integer-valued float
    payloads: aggregation order cannot perturb bits);
  * no overflow and no safe-capacity retry (the estimates held).

Tables are deliberately HALF-FULL (capacity = 2x rows): the fixed-slack
path can only see capacity, the stats path knows the true row count —
the structural advantage this benchmark quantifies.

Each measurement runs in a fresh subprocess: the 8-device host platform
must be fixed before jax initializes.
"""
from __future__ import annotations

import json
import sys

from benchmarks.common import Table, run_cpu_worker

WORKERS = 8
AGGS = (("d0", "sum"), ("d0", "count"), ("d0", "min"), ("d0", "max"))


def run_worker(rows_per_worker: int, key_range: int) -> dict:
    return run_cpu_worker(
        ["-m", "benchmarks.bench_cost", "--worker",
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
    rows, kr = args.rows_per_worker, args.key_range

    def int_table(seed):
        """Integer-valued float payloads (bit-exact sums), half-full."""
        rng = np.random.default_rng(seed)
        return T.from_arrays({
            "k": rng.integers(0, kr, rows).astype(np.int32),
            "d0": rng.integers(-40, 40, rows).astype(np.float32)},
            capacity=2 * rows)

    raw = ctx.from_local_parts([int_table(100 + i) for i in range(WORKERS)])
    analyzed = ctx.analyze(raw)

    base = ctx.frame(raw).groupby("k", AGGS)        # fixed-slack fallback
    cost = ctx.frame(analyzed).groupby("k", AGGS)   # stats-driven

    strategy = cost.optimized().strategy
    base_rep, cost_rep = base.plan_report(), cost.plan_report()
    base_wire = sum(r["wire_bytes"] for r in base_rep)
    cost_wire = sum(r["wire_bytes"] for r in cost_rep)

    eager, _ = ctx.groupby(raw, "k", AGGS)  # the oracle both must match
    b_out = base.collect()
    c_out, c_stats = cost.collect_with_stats()
    overflow = sum(int(np.asarray(s.overflow).sum()) for s in c_stats)

    from repro.testing.compare import tables_bitwise_equal
    secs_base = timeit(lambda: base.collect().row_counts, warmup=1, iters=3)
    secs_cost = timeit(lambda: cost.collect().row_counts, warmup=1, iters=3)

    print("RESULT:" + json.dumps({
        **device_record(),
        "rows": rows * WORKERS, "key_range": kr,
        "groups": int(np.asarray(c_out.global_rows())),
        "strategy": strategy,
        "base_wire_mb": base_wire / 1e6, "cost_wire_mb": cost_wire / 1e6,
        "base_seconds": secs_base, "cost_seconds": secs_cost,
        "identical": bool(tables_bitwise_equal(eager, c_out)
                          and tables_bitwise_equal(eager, b_out)),
        "overflow": overflow, "retries": ctx.overflow_retries,
    }))


def main(quick: bool = False):
    rpw = 1_000 if quick else 10_000
    # sweep ends: NDV 32 (p*ndv << rows -> two_phase) up to a key range
    # several times the global row count (ndv ~ rows -> raw shuffle)
    sweep = [(32, "two_phase"), (rpw * WORKERS * 4, "shuffle")]
    t = Table(
        f"cost-model planning (P={WORKERS}, {rpw} rows/worker, half-full "
        "capacity): stats-driven strategy choice + right-sized buckets vs "
        "the fixed-slack no-stats baseline",
        ["key_range", "strategy", "groups", "base_wire_mb", "cost_wire_mb",
         "wire_reduction", "base_seconds", "cost_seconds", "identical"])
    for kr, expect in sweep:
        r = run_worker(rpw, kr)
        assert r["strategy"] == expect, (kr, expect, r)
        assert r["identical"], r
        assert r["overflow"] == 0 and r["retries"] == 0, r
        assert r["cost_wire_mb"] < r["base_wire_mb"], r
        t.add(kr, r["strategy"], r["groups"], round(r["base_wire_mb"], 4),
              round(r["cost_wire_mb"], 4),
              round(r["base_wire_mb"] / max(r["cost_wire_mb"], 1e-9), 1),
              r["base_seconds"], r["cost_seconds"], r["identical"])
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
                           "sections": {"cost": [table.to_dict()]}},
                          f, indent=2, default=str)
            print(f"[json] wrote {args.json}")
