"""Profile one cell's window and attribute its time to the engine.

    python3 bench/engine_profile.py --workload q12_sf40_4chip --seed 7 \
        --seconds 10 --out q12.engine.json.gz

One ``bench/run.py --trace 1`` run (``bench.run.execute``), printed with
the engine's attribution in full: device time per operator scope and
step, the mean of each engine host span, idle gaps named by engine
spans. Prints one JSON line; ``--out`` also writes the window's records
with the scope and category maps of the ops they hold (gzip JSON), to
reduce again or to cut into a test fixture. Without a TPU, or with fewer
chips than the cell asks for, the run fails.
"""
from __future__ import annotations

import argparse
import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as R  # noqa: E402
from bench import tracing  # noqa: E402


def _kept(maps: dict, records: dict) -> dict:
    """The entries of per-module maps for the ops the records hold."""
    names = {tracing.op_name(t) for ops in records["ops"].values()
             for t, _, _ in ops}
    return {m: {k: v for k, v in mp.items() if k in names}
            for m, mp in maps.items()}


def profile(args, **kw) -> dict:
    """One traced run of ``bench.run.execute`` (``kw`` passed on), with
    its attribution."""
    run_args = R.parse_args(["--workload", args.workload, "--seed",
                             str(args.seed), "--seconds", str(args.seconds),
                             "--trace", "1"])
    traced: dict = {}
    line = R.execute(run_args, traced=traced, **kw)
    s, records, maps = traced["summary"], traced["records"], traced["maps"]
    if args.out:
        with gzip.open(args.out, "wt") as f:
            json.dump({"queries": s.queries, "records": records,
                       "spans": traced["spans"],
                       "scopes": _kept(maps["scopes"], records),
                       "categories": _kept(maps["categories"], records)}, f)
    got = line["metrics"]
    return {
        "correct": line["correct"], "device": line["device"],
        "metrics": {m["name"]: got[m["name"]]["value"]
                    if m["name"] in got else None
                    for m in R.load_cell(args.workload)["per_layer"]},
        "coverage": s.coverage, "operator_s": s.operator_s,
        "step_s": s.step_s,
        "engine_span_ms": {k: 1e3 * sum(v) / len(v)
                           for k, v in sorted(s.engine_span_s.items())},
        "idle_gaps": s.idle_gaps}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="write the window's records here "
                    "(gzip JSON)")
    args = ap.parse_args(argv)
    try:
        line = profile(args)
    except Exception as e:  # noqa: BLE001 — any failure: no result line
        import traceback

        traceback.print_exc()
        R.log(f"engine profile FAILED: {e!r}")
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
