"""Readings that the limits of ``correct`` are set from.

    python3 bench/calibrate.py --workload q12_sf40_4chip --seeds 1,2,3

For each seed, in one process: the cell's set-up and its timed path
(``Run.one_query``, as in a benchmark run, at the cell's own size; the
first query is not set apart as a warm-up), each result compared with the query's reference; then the query's control
(the reference in a lower precision, or with a guarantee broken) compared
with the same reference. One JSON line per seed: the program's numbers,
the control's, and, where the query gives them, the per-column errors.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

from bench.run import Run, _to_host  # noqa: E402


def worst(rows: list[dict]) -> dict:
    out: dict = {}
    for r in rows:
        for k, v in r.items():
            out[k] = max(out.get(k, float("-inf")), v)
    return out


def calibrate(workload: str, seed: int, queries: int, *,
              require_tpu: bool = True, config_override=None) -> dict:
    import jax

    run_ = Run(workload, seed, require_tpu=require_tpu,
               config_override=config_override)
    kept = [jax.device_get(run_.one_query()) for _ in range(queries)]
    host = run_.host_inputs()
    run_.free()
    q, params = run_.query, run_.params
    want = q.reference(host, params)
    got = [_to_host(k)[0] for k in kept]
    line = {"seed": seed,
            "program": worst([q.compare(g, want) for g in got]),
            "control": q.compare(q.control(host, params), want)}
    if hasattr(q, "column_errors"):
        line["program_columns"] = worst([q.column_errors(g, want)
                                         for g in got])
        line["control_columns"] = q.column_errors(q.control(host, params),
                                                  want)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--queries", type=int, default=1,
                    help="timed-path queries compared per seed")
    args = ap.parse_args(argv)
    from repro.utils import use_compile_cache

    use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(calibrate(args.workload, seed, args.queries)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
