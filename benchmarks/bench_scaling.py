"""Paper Figs. 7 & 8: weak + strong scaling of distributed Join (hash &
sort) and Union over SPMD worker counts.

Caveat (recorded in EXPERIMENTS.md): this container exposes ONE physical
core, so the P "devices" time-share it — wall-clock cannot show speedup.
The curves validate the BSP structure (flat per-worker cost would appear
on real chips), and the per-worker collective bytes from the compiled HLO
(bench output column) are the hardware-independent scaling signal.
"""
from __future__ import annotations

import sys

from benchmarks.common import Table, run_cpu_worker

WORKER_COUNTS = [1, 2, 4, 8]
OPS = ["join_hash", "join_sort", "union"]


def run_worker(op: str, workers: int, rows_per_worker: int) -> dict:
    return run_cpu_worker(
        ["-m", "benchmarks.scaling_worker", "--op", op,
         "--workers", str(workers), "--rows-per-worker",
         str(rows_per_worker)],
        workers)


def bench_weak(rows_per_worker: int = 50_000) -> Table:
    t = Table("Fig7: weak scaling (rows/worker fixed = %d)" % rows_per_worker,
              ["op", "workers", "total_rows", "seconds", "rows_per_sec"])
    for op in OPS:
        for p in WORKER_COUNTS:
            r = run_worker(op, p, rows_per_worker)
            t.add(op, p, r["total_rows"], r["seconds"], r["rows_per_second"])
    return t


def bench_strong(total_rows: int = 200_000) -> Table:
    t = Table("Fig8: strong scaling (total rows fixed = %d)" % total_rows,
              ["op", "workers", "rows_per_worker", "seconds", "speedup"])
    for op in OPS:
        base = None
        for p in WORKER_COUNTS:
            r = run_worker(op, p, total_rows // p)
            if base is None:
                base = r["seconds"]
            t.add(op, p, total_rows // p, r["seconds"], base / r["seconds"])
    return t


def main(quick: bool = False):
    rpw = 20_000 if quick else 50_000
    tot = 80_000 if quick else 200_000
    weak, strong = bench_weak(rpw), bench_strong(tot)
    weak.emit()
    strong.emit()
    return [weak, strong]


if __name__ == "__main__":
    main("--quick" in sys.argv)
