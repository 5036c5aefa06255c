"""Profile one cell's window and attribute its time to the engine.

    python3 bench/engine_profile.py --workload q12_sf40_4chip --seed 7 \
        --seconds 10 --out q12.engine.json.gz

The set-up, warm-up and closed window of ``bench/run.py``, under the
profiler, reduced two ways: by ``bench.tracing`` into the benchmark's
per-layer metrics, and by ``bench.scopes`` into the engine's own
attribution (device time per operator scope and step, host time per
engine span, idle gaps named by engine spans). Prints one JSON line;
``--out`` also writes the window's records with the scope and category
maps of the ops they hold (gzip JSON), to reduce again or to cut into a
test fixture. Results are not checked here: ``bench/run.py`` does that.
Without a TPU, or with fewer chips than the cell asks for, the run fails.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as R  # noqa: E402
from bench import scopes, tracing  # noqa: E402


def _compile_log(label: str, stats: dict, before: dict | None = None):
    base = before or {"misses": 0, "compile_s": 0.0}
    R.log(f"{label}: plan-cache misses {stats['misses'] - base['misses']}, "
          f"compile_s {stats['compile_s'] - base['compile_s']:.6f}")


def _maps(run_) -> tuple[dict, dict]:
    """Module name -> instruction categories and scopes, from one compile
    of each program the query's plans admitted (a persistent-cache hit)."""
    frame = run_.query.build(run_.session, run_.params)
    args = tuple((t.columns, t.row_counts) for t in frame._inputs)
    cats, scope_maps = {}, {}
    for jitted in run_.plan_cache.admitted:
        text = jitted.lower(*args).compile().as_text()
        module = text.split(None, 2)[1].rstrip(",")
        cats.setdefault(module, {}).update(tracing.hlo_categories(text))
        scope_maps.setdefault(module, {}).update(scopes.hlo_scopes(text))
    return cats, scope_maps


def _kept(maps: dict, records: dict) -> dict:
    """The entries of per-module maps for the ops the records hold."""
    names = {tracing.op_name(t) for ops in records["ops"].values()
             for t, _, _ in ops}
    return {m: {k: v for k, v in mp.items() if k in names}
            for m, mp in maps.items()}


def profile(args, *, require_tpu: bool = True,
            config_override: dict | None = None) -> dict:
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax

    from repro.utils import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    run_ = R.Run(args.workload, args.seed, require_tpu=require_tpu,
                 config_override=config_override)
    with run_.spans("bench.warmup"):
        run_.warm_up()
    before = run_.ctx.cache_stats()
    _compile_log("set-up", before)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    times = []
    with run_.spans(tracing.WINDOW_SPAN):
        t0 = t_last = time.perf_counter()
        while t_last - t0 < args.seconds:
            t_q = time.perf_counter()
            jax.block_until_ready(run_.one_query())
            t_last = time.perf_counter()
            times.append(t_last - t_q)
    jax.profiler.stop_trace()
    _compile_log("window", run_.ctx.cache_stats(), before)
    try:
        records = tracing.load(trace_dir)
        spans = scopes.load_spans(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    cats, scope_maps = _maps(run_)

    queries = len(times)
    summary = tracing.summarize(records, queries=queries, categories=cats)
    existing = {}
    for m in run_.spec["per_layer"]:
        reader = R.load_module(R.BENCH / "metrics" / f"{m['name']}.py")
        existing[m["name"]] = reader.read(summary)
    eng = scopes.summarize(records, spans, queries, scope_maps)
    if args.out:
        with gzip.open(args.out, "wt") as f:
            json.dump({"queries": queries, "records": records,
                       "spans": spans, "scopes": _kept(scope_maps, records),
                       "categories": _kept(cats, records)}, f)
    return {
        "device": {"platform": run_.platform,
                   "kind": run_.devices[0].device_kind,
                   "count": len(run_.devices)},
        "seconds_per_query": times,
        "busy_s": eng["busy_s"], "window_s": eng["window_s"],
        "metrics": existing, "engine_metrics": scopes.metrics(eng),
        "coverage": scopes.coverage(eng),
        "operator_s": eng["operator_s"], "step_s": eng["step_s"],
        "engine_span_ms": {k: 1e3 * sum(v) / len(v)
                           for k, v in sorted(eng["span_s"].items())},
        "idle_gaps": eng["idle_gaps"]}


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
