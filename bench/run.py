"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload q12_sf40_4chip --seed 7 --seconds 10 \
        --trace 0

A cell names a deployment (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<mix>.json``), both found by name through
``BENCHMARK.json``; the mix names its query (``bench/queries/<q>.py``)
and the configuration its generator (``bench/gen/<g>.py``). Per-layer
metrics are the readers ``bench/metrics/<metric>.py``. Nothing here
branches on the name of a cell, a configuration, a mix or a metric.

One run, in one process:

1. generate the deployment's tables on the device from ``--seed``;
2. register them with ``ServingSession.register(..., analyze=True)``;
3. warm up the mix's query through the timed path (compiled programs come
   from the persistent cache after a checkout's first run);
4. run the window: one client, one query in flight, each
   ``collect_async()`` -> ``PlanFuture.result()`` -> ``block_until_ready``,
   started back to back while fewer than ``--seconds`` have passed; the
   last one finishes;
5. compare every result of the window with the query's plain NumPy
   reference, once the window has closed and the device state is freed;
6. print one JSON line: ``--trace 0`` gives the end-to-end metrics,
   ``--trace 1`` the per-layer metrics read from a profiler trace of the
   window.

A reader is ``read(summary)`` over a :class:`bench.tracing.Summary`: the
window's device time by opcode category and the harness's spans
(``bench.tracing``), device time by the engine's operator and step scopes
and the engine's host spans (``bench.scopes``), and the device seconds
and logical bytes of each jitted function the programs call, with the
chip's HBM bandwidth (``bench.roofline``). It returns None where it finds
nothing to read, and the metric is then left out of the line.

Without a TPU, or with fewer chips than the cell asks for, the run fails
and prints no result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import roofline, scopes, tracing  # noqa: E402

AXIS = "shuffle"


class BenchError(Exception):
    """A run that can give no result: no chip, a bad spec, a lost query."""


def load_module(path: Path):
    name = "bench_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise BenchError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, found by name from ``BENCHMARK.json``."""
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path}")
    spec = json.loads(path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    return {"cell": cell, "config": config, "mix": mix,
            "end_to_end": [m for m in spec["end_to_end"]
                           if _applies(m, name)],
            "per_layer": [m for m in spec["per_layer"] if _applies(m, name)],
            "query": load_module(BENCH / "queries" / f"{mix['query']}.py"),
            "generator": load_module(BENCH / "gen"
                                     / f"{config['generator']}.py")}


def peaks(kind: str) -> dict:
    """The published peaks of one chip of ``kind`` (``bench/peaks.json``);
    a device that is not in the table is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def _head(chips: int, k: int, columns: dict, row_counts):
    """The first ``k`` rows of each shard: what the run keeps of a result
    (a groupby's output keeps its input's capacity)."""
    return ({n: c.reshape((chips, -1) + c.shape[1:])[:, :k]
             for n, c in columns.items()}, row_counts)


def _to_host(kept) -> tuple[dict, int]:
    """Valid rows of a kept head, and how many rows lay beyond it."""
    cols, rc = kept
    rc = np.asarray(rc)
    out = {}
    for n, c in cols.items():
        a = np.asarray(c)
        out[n] = np.concatenate([a[i, :min(int(r), a.shape[1])]
                                 for i, r in enumerate(rc)])
    k = next(iter(cols.values())).shape[1]
    return out, int(np.maximum(rc - k, 0).sum())


class Run:
    """One cell's set-up and timed path, in this process."""

    def __init__(self, name: str, seed: int, *, require_tpu: bool = True,
                 config_override: dict | None = None):
        self.spec = load_cell(name)
        if config_override:
            self.spec["config"] = {**self.spec["config"], **config_override}
        self.spans = _Spans()
        cell = self.spec["cell"]
        self.chips = cell["chips"]

        import jax
        from jax.sharding import AxisType, Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        devices = jax.devices()
        self.platform = devices[0].platform
        if require_tpu and self.platform != "tpu":
            raise BenchError(f"no TPU: JAX found {len(devices)} "
                             f"{self.platform} device(s)")
        if len(devices) < self.chips:
            raise BenchError(f"{cell['name']} needs {self.chips} chips; "
                             f"JAX found {len(devices)}")
        self.devices = devices
        self.used = devices[:self.chips]
        self.peaks = peaks(devices[0].device_kind) if require_tpu else None
        from repro.core.context import DistContext, DistTable
        from repro.core.plan_cache import PlanCache
        from repro.core.serving import ServingSession

        class RecordingCache(PlanCache):
            """Keeps every program admitted, to read its compiled text, and
            counts the runs of each (by ``id``): the first call that
            admits it and every hit."""

            def __init__(self):
                super().__init__()
                self.admitted = []
                self.runs = collections.Counter()

            def get(self, key):
                value = super().get(key)
                if value is not None:
                    self.runs[id(value)] += 1
                return value

            def put(self, key, value, **kw):
                self.admitted.append(value)
                self.runs[id(value)] += 1
                return super().put(key, value, **kw)

        self.mesh = Mesh(np.asarray(self.used), (AXIS,),
                         axis_types=(AxisType.Auto,))
        self.plan_cache = RecordingCache()
        self.ctx = DistContext(mesh=self.mesh, axis_name=AXIS,
                               plan_cache=self.plan_cache)
        self.session = ServingSession(self.ctx)
        mix, query = self.spec["mix"], self.spec["query"]
        self.params = mix["params"]
        self.query = query

        with self.spans("bench.generate"):
            self.tables = self.spec["generator"].generate(
                self.spec["config"], seed, self.mesh, AXIS)
            jax.block_until_ready(self.tables)
        with self.spans("bench.register"):
            rc_sharding = NamedSharding(self.mesh, P(AXIS))
            for tname, (cols, rows) in self.tables.items():
                rc = jax.device_put(np.full(self.chips, rows, np.int32),
                                    rc_sharding)
                self.session.register(tname, DistTable(cols, rc),
                                      analyze=True)
        self.rows_scanned = self.chips * self.tables[mix["scan_table"]][1]
        self._head = jax.jit(functools.partial(
            _head, self.chips, int(mix["result_rows_per_shard"])))

    def one_query(self):
        """The timed path: submit, wait for the verified result, keep its
        head. Returns the kept head (device arrays, dispatched)."""
        import jax

        with self.spans("bench.submit"):
            fut = self.query.build(self.session, self.params).collect_async()
        with self.spans("bench.wait"):
            out = fut.result()
            jax.block_until_ready(out.columns)
        with self.spans("bench.retain"):
            return self._head(out.columns, out.row_counts)

    def warm_up(self):
        import jax

        for _ in range(int(self.spec["mix"]["warmup_queries"])):
            jax.block_until_ready(self.one_query())

    def program_maps(self, since: dict[int, int] | None = None) -> dict:
        """What the programs the query's plans admitted say of their
        instructions, each traced and compiled again (a persistent-cache
        hit): per module name, the instruction categories
        (``categories``), operator scopes (``scopes``) and jitted calls
        (``calls``); and ``bytes``, the calls' logical bytes per device
        over the runs since ``since``, a copy of ``plan_cache.runs``."""
        frame = self.query.build(self.session, self.params)
        args = tuple((t.columns, t.row_counts) for t in frame._inputs)
        maps = {"categories": {}, "scopes": {}, "calls": {}, "bytes": {}}
        readers = {"categories": tracing.hlo_categories,
                   "scopes": scopes.hlo_scopes, "calls": roofline.hlo_calls}
        for jitted in self.plan_cache.admitted:
            traced = jitted.trace(*args)
            text = traced.lower().compile().as_text()
            module = text.split(None, 2)[1].rstrip(",")
            for key, read in readers.items():
                maps[key].setdefault(module, {}).update(read(text))
            ran = self.plan_cache.runs[id(jitted)]
            n = ran - since.get(id(jitted), 0) if since is not None else 0
            if n:
                maps["bytes"] = roofline.add_bytes(
                    maps["bytes"],
                    roofline.call_bytes(traced.jaxpr, self.chips), n)
        return maps

    def host_inputs(self) -> dict:
        """Host copies of the input columns the reference reads."""
        out = {t: {c: np.asarray(self.tables[t][0][c]) for c in cols}
               for t, cols in self.query.READS.items()}
        out["chips"] = self.chips
        return out

    def memory_peak(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.used]
        return int(max(peaks))

    def free(self):
        """Drop the program's state and the tables."""
        self.session = self.ctx = self.tables = None


class _Spans:
    """Harness host spans: a ``TraceAnnotation`` each (read back from a
    profiler trace) and a host-clock record of the set-up ones."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation(name):
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) \
            + time.perf_counter() - t0


def attribute(summary: tracing.Summary, records: dict, spans: list,
              maps: dict, hbm_bytes_per_s: float | None) -> tracing.Summary:
    """``summary`` with the engine's attribution of the same window
    (``bench.scopes``; its top ops labelled with their operator and step,
    its idle gaps named by engine spans too) and the seconds and bytes of
    the jitted functions the programs call (``bench.roofline``) attached.
    ``maps`` is :meth:`Run.program_maps`."""
    eng = scopes.summarize(records, spans, summary.queries, maps["scopes"])
    scope_of = {k: v for sc in maps["scopes"].values() for k, v in sc.items()}
    labelled = []
    for op, secs in summary.device_ops:  # "fusion.3 [gather]"
        names = [n for n in scope_of.get(op.split(" ")[0]) or () if n]
        labelled.append([f"{op} {'/'.join(names)}" if names else op, secs])
    return dataclasses.replace(
        summary, operator_s=eng["operator_s"], step_s=eng["step_s"],
        engine_span_s=eng["span_s"], scoped_s=eng["scoped_s"],
        device_ops=labelled, idle_gaps=eng["idle_gaps"],
        call_s=roofline.call_seconds(records, maps["calls"]),
        call_bytes=maps["bytes"], hbm_bytes_per_s=hbm_bytes_per_s)


def judge(run_: Run, kept: list, host: dict) -> dict:
    """Compare every kept result with the reference: the worst of each
    number over the results, each beside its limit."""
    q = run_.query
    want = q.reference(host, run_.params)
    worst: dict[str, float] = {}
    lost_rows = 0
    for k in kept:
        got, lost = _to_host(k)
        lost_rows += lost
        for n, v in q.compare(got, want).items():
            worst[n] = max(worst.get(n, -np.inf), v)
    worst["result_rows_lost"] = lost_rows
    limits = dict(run_.spec["mix"]["limits"], result_rows_lost=0)
    return {n: {"value": worst[n], "limit": limits[n]} for n in limits
            if n in worst}


def passes(checks: dict) -> bool:
    return all(c["limit"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def execute(args, *, require_tpu: bool = True,
            config_override: dict | None = None,
            traced: dict | None = None) -> dict:
    """One run; returns the result line as a dict. A trace run leaves
    its window's ``records``, engine ``spans``, program ``maps`` and
    ``summary`` in ``traced``, where given."""
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax

    from repro.utils import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    run_ = Run(args.workload, args.seed, require_tpu=require_tpu,
               config_override=config_override)
    with run_.spans("bench.warmup"):
        run_.warm_up()
    log(f"device: platform={run_.platform} "
        f"kind={run_.devices[0].device_kind} count={len(run_.devices)} "
        f"used={run_.chips}")
    log(f"set-up seconds: " + ", ".join(
        f"{k} {v:.6f}" for k, v in run_.spans.seconds.items()))
    ctx = run_.ctx
    before = ctx.cache_stats()
    runs_before = dict(run_.plan_cache.runs)

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    kept, times, attempted, failed = [], [], 0, 0
    with run_.spans(tracing.WINDOW_SPAN):
        t0 = time.perf_counter()
        setup_s = t0 - T_START
        t_last = t0
        while t_last - t0 < args.seconds:
            attempted += 1
            t_q = time.perf_counter()
            try:
                kept.append(run_.one_query())
            except Exception as e:  # noqa: BLE001 — a lost query is counted
                failed += 1
                log(f"query {attempted} failed: {e!r}")
            t_last = time.perf_counter()
            times.append(t_last - t_q)
    if args.trace:
        jax.profiler.stop_trace()
    window_s = t_last - t0
    after = ctx.cache_stats()
    peak = run_.memory_peak()
    log(f"window: {len(kept)} queries in {window_s:.6f} s; seconds per "
        f"query: {', '.join(f'{t:.6f}' for t in times)}")
    log(f"compiles in the window (plan-cache misses): "
        f"{after['misses'] - before['misses']}, compile_s "
        f"{after['compile_s'] - before['compile_s']:.6f}; in set-up: "
        f"{before['misses']}, compile_s {before['compile_s']:.6f}")
    log("recovery counters: " + ", ".join(
        f"{k} {after[k]}" for k in (
            "overflow_retries", "degraded_kernel", "degraded_shuffle",
            "compile_retries", "generic_retries", "quarantines",
            "failed_queries")))
    share = f" ({100 * peak / run_.peaks['hbm_bytes']:.3f}% of one chip's " \
        f"HBM)" if run_.peaks else ""
    log(f"peak_bytes_in_use: {peak}{share}")

    maps = run_.program_maps(runs_before) if args.trace else None
    t_check = time.perf_counter()
    host = run_.host_inputs()
    run_.free()
    kept_host = [jax.device_get(k) for k in kept]
    del kept
    checks = judge(run_, kept_host, host) if kept_host else {}
    log(f"reference and comparison: {time.perf_counter() - t_check:.6f} s")
    correct = bool(kept_host) and failed == 0 and passes(checks)

    device = {"platform": run_.platform,
              "kind": run_.devices[0].device_kind,
              "count": len(run_.devices), "memory_peak_bytes": peak}
    line = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        try:
            records = tracing.load(trace_dir)
            spans = scopes.load_spans(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        summary = attribute(
            tracing.summarize(records, queries=len(kept_host),
                              categories=maps["categories"]),
            records, spans, maps,
            run_.peaks["hbm_bytes_per_s"] if run_.peaks else None)
        if traced is not None:
            traced.update(records=records, spans=spans, maps=maps,
                          summary=summary)
        metrics = {}
        for m in run_.spec["per_layer"]:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            v = reader.read(summary)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        line.update(metrics=metrics, device=device,
                    breakdown={"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps})
        log(f"trace: busy {summary.busy_s:.6f} s of {summary.window_s:.6f} "
            f"s; device seconds by category: {summary.category_s}")
        log(f"engine scopes: coverage {summary.coverage:.6f} "
            f"({summary.scoped_s:.6f} s of busy {summary.busy_s:.6f} s); "
            f"device seconds by operator: {summary.operator_s}; by step: "
            f"{summary.step_s}")
        log("jitted calls (device seconds, bytes per device): " + ", ".join(
            f"{n} {s:.6f} {summary.call_bytes.get(n)}"
            for n, s in summary.call_s.items()))
    else:
        values = {"setup_s": setup_s,
                  "rows_per_s": len(kept_host) * run_.rows_scanned
                  / window_s}
        line.update(metrics={m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in run_.spec["end_to_end"]},
                    device=device)
    line["checks"] = checks
    for n, c in checks.items():
        log(f"check {n}: {c['value']!r} (limit {c['limit']!r})")
    return line


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        line = execute(args)
    except Exception as e:  # noqa: BLE001 — any failure: no result line
        import traceback

        traceback.print_exc()
        log(f"bench run FAILED: {e!r}")
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
