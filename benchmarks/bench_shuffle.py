"""Staged (pipelined) shuffles vs the monolithic AllToAll on 8 devices.

The repartition exchange splits its ``(p, bucket)`` send buckets into S
chunks along the capacity axis — one collective per chunk — so XLA can
overlap one chunk's wire time with its neighbours' pack/unpack compute
inside the single fused shard_map program (plus a ``ppermute``-ring
strategy for comparison). The contract is bit-identity: every (stages,
shuffle_mode) produces the same rows, the same overflow accounting, and
the same dense wire bytes — staging only re-chunks the collective.

The table reports per-mode AllToAll/ppermute counts (from the traced
jaxpr), plan_report wire bytes, wall clock, and the bitwise row-multiset
check. Asserts — also enforced when CI uploads the JSON — that S=1 issues
exactly one collective per column (the folded-counts program: no extra
counts exchange, no added AllToAll), that staged and ring runs are
bit-identical to monolithic, and that wire bytes match across modes.

Each measurement runs in a fresh subprocess: the 8-device host platform
must be fixed before jax initializes.
"""
from __future__ import annotations

import json
import sys

from benchmarks.common import Table, run_cpu_worker

WORKERS = 8


def run_worker(rows_per_worker: int, stages: int) -> dict:
    return run_cpu_worker(
        ["-m", "benchmarks.bench_shuffle", "--worker",
         "--rows-per-worker", str(rows_per_worker),
         "--stages", str(stages)],
        WORKERS)


def _worker_main(argv) -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--rows-per-worker", type=int, required=True)
    ap.add_argument("--stages", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from benchmarks.common import device_record, timeit
    from repro.core import ops_dist as D
    from repro.core.context import DistContext
    from repro.core.table import Table as T
    from repro.testing.compare import tables_bitwise_equal
    from repro.utils import shard_map

    assert jax.device_count() == WORKERS, jax.device_count()
    ctx = DistContext(axis_name="shuffle")
    cap, staged_s = args.rows_per_worker, args.stages

    def part(seed):
        rng = np.random.default_rng(seed)
        return T.from_arrays({
            "k": rng.integers(0, cap * 4, cap).astype(np.int32),
            # (cap, 8) payload: enough bytes/row that the exchange (not
            # the pack) dominates, the regime staging targets
            "v": rng.integers(-50, 50, (cap, 8)).astype(np.float32)})

    parts = [part(100 + i) for i in range(WORKERS)]
    dt = ctx.from_local_parts(parts)
    bucket = 2 * cap  # skew-proof: no overflow, latency compares clean

    modes = (("mono", dict(stages=1)),
             ("staged", dict(stages=staged_s)),
             ("ring", dict(shuffle_mode="ring")))

    # collective counts from the traced program, per mode
    mesh, ax = ctx.mesh, ctx.axis_name
    gk = np.concatenate([np.asarray(q.columns["k"]) for q in parts])
    gv = np.concatenate([np.asarray(q.columns["v"]) for q in parts])
    grc = np.full((WORKERS,), cap, np.int32)

    # the shared traced-jaxpr collective counters (also what
    # verify.audit_collectives uses to cross-check plan_report)
    from repro.core.verify import count_collectives

    def counts_for(kw):
        def body(k, v, rc):
            tab = T({"k": k, "v": v}, rc[0])
            out, _ = D.dist_repartition_by(
                tab, ["k"], axis_name=ax, bucket_capacity=bucket, **kw)
            return out.columns["k"]

        with mesh:
            jaxpr = str(jax.make_jaxpr(shard_map(
                body, mesh=mesh, in_specs=(P(ax), P(ax), P(ax)),
                out_specs=P(ax)))(gk, gv, grc))
        c = count_collectives(jaxpr)
        return c["all_to_all"], c["ppermute"]

    out = {"rows": cap * WORKERS, "bucket": bucket, "stages": staged_s}
    results = {}
    for name, kw in modes:
        rep: list = []
        res, (st,) = ctx.partition_by(dt, "k", bucket_capacity=bucket,
                                      report=rep, **kw)
        a2a, pperm = counts_for(kw)
        secs = timeit(
            lambda kw=kw: ctx.partition_by(dt, "k", bucket_capacity=bucket,
                                           **kw)[0].row_counts,
            warmup=2, iters=5)
        results[name] = res
        out[name] = {
            "alltoalls": a2a, "ppermutes": pperm,
            "wire_mb": rep[0]["wire_bytes"] / 1e6,
            "report_stages": rep[0]["stages"], "mode": rep[0]["mode"],
            "overflow": int(np.asarray(st.overflow).sum()),
            "seconds": secs,
        }

    n_cols = 2  # k + v: the folded-counts program is 1 collective/column
    out["mono_collectives_ok"] = out["mono"]["alltoalls"] == n_cols
    out["staged_chunked"] = out["staged"]["alltoalls"] > out["mono"]["alltoalls"]
    out["ring_no_alltoall"] = out["ring"]["alltoalls"] == 0 \
        and out["ring"]["ppermutes"] > 0
    out["staged_identical"] = tables_bitwise_equal(results["mono"],
                                                   results["staged"])
    out["ring_identical"] = tables_bitwise_equal(results["mono"],
                                                 results["ring"])
    out["wire_identical"] = (out["mono"]["wire_mb"] == out["staged"]["wire_mb"]
                             == out["ring"]["wire_mb"])
    print("RESULT:" + json.dumps({**out, **device_record()}))


def main(quick: bool = False):
    rpw = 4_000 if quick else 50_000
    stages = 4
    t = Table(
        f"staged shuffle (P={WORKERS}, {rpw} rows/worker, 36 B/row): "
        f"S={stages} pipelined chunks and the ppermute ring vs one "
        "monolithic AllToAll — bit-identical rows, identical wire bytes, "
        "only the collective decomposition differs",
        ["mode", "stages", "alltoalls", "ppermutes", "wire_mb", "seconds",
         "identical"])
    r = run_worker(rpw, stages)
    # the contract gates (CI fails on any of these):
    assert r["mono_collectives_ok"], \
        f"S=1 must be 1 collective/column (counts folded): {r['mono']}"
    assert r["staged_chunked"], r
    assert r["ring_no_alltoall"], r
    assert r["staged_identical"] and r["ring_identical"], \
        "staged/ring shuffle not bit-identical to monolithic"
    assert r["wire_identical"], r
    for name in ("mono", "staged", "ring"):
        m = r[name]
        assert m["overflow"] == 0, (name, m["overflow"])
        t.add(name, m["report_stages"], m["alltoalls"], m["ppermutes"],
              round(m["wire_mb"], 3), m["seconds"],
              True if name == "mono" else r[f"{name}_identical"])
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
                           "sections": {"shuffle": [table.to_dict()]}},
                          f, indent=2, default=str)
            print(f"[json] wrote {args.json}")
