"""The engine's attribution (``bench.scopes``): operator scopes from the
compiled text, engine spans from the trace, and a CPU run of
``bench/engine_profile.py``."""
import argparse
import json

import pytest

from bench import scopes, tracing
from bench.run import BENCH, attribute, load_module

#: the per-layer metrics read from the engine's scopes and spans
ENGINE_METRICS = ("filter_ms.batch", "join_ms.batch", "groupby_ms.batch",
                  "exchange_ms.batch", "plan_host_ms.batch",
                  "dispatch_host_ms.batch")

HLO = "\n".join([
    "HloModule jit_global_fn, is_scheduled=true",
    "%fused_computation (p0: s32[8], p1: s32[8]) -> s32[8] {",
    "  %p0 = s32[8]{0} parameter(0)",
    "  ROOT %gather.1 = s32[8]{0} gather(s32[8]{0} %p0, s32[8] %p1),"
    ' metadata={op_name="jit(global_fn)/shard_map/engine.join/'
    'engine.step.emit/gather"}',
    "}",
    "%region_0 (a: s32[8]) -> s32[8] {",
    "  ROOT %gather.2 = s32[8]{0} gather(s32[8]{0} %a, s32[8] %a),"
    ' metadata={op_name="jit(global_fn)/shard_map/engine.join/'
    'engine.step.search/jit(searchsorted)/vmap()/while/body/gather"}',
    "}",
    "ENTRY %main.3 (a: s32[8], i: s32[8]) -> s32[8] {",
    "  %fusion = s32[8]{0} fusion(s32[8]{0} %a, s32[8]{0} %i),"
    " kind=kLoop, calls=%fused_computation,"
    ' metadata={op_name="jit(global_fn)/shard_map/engine.join/'
    'engine.step.emit/gather"}',
    "  %all-to-all.4 = s32[8]{0} all-to-all(s32[8]{0} %fusion),"
    ' metadata={op_name="jit(global_fn)/shard_map/engine.join/'
    'engine.exchange/all_to_all"}',
    "  %sort.5 = s32[8]{0} sort(s32[8]{0} %a), metadata={op_name="
    '"jit(global_fn)/shard_map/engine.join/engine.exchange/'
    'engine.step.compact/jit(argsort)/sort"}',
    "  %while.6 = s32[8]{0} while(s32[8]{0} %a), body=%region_0,"
    ' metadata={op_name="jit(global_fn)/shard_map/engine.join/'
    'engine.step.search/jit(searchsorted)/vmap()/while"}',
    "  %copy.7 = s32[8]{0} copy(s32[8]{0} %while.6)",
    "  ROOT %reduce.8 = s32[8]{0} reduce(s32[8]{0} %copy.7),"
    ' metadata={op_name="jit(global_fn)/shard_map/engine.groupby/'
    'reduce_sum"}',
    "}"])


def test_hlo_scopes_take_the_innermost_operator():
    sc = scopes.hlo_scopes(HLO)
    # a fusion and its root carry the same metadata
    assert sc["fusion"] == sc["gather.1"] == ["engine.join",
                                              "engine.step.emit"]
    # an exchange inside a join is the exchange's; its step refines it
    assert sc["all-to-all.4"] == ["engine.exchange", None]
    assert sc["sort.5"] == ["engine.exchange", "engine.step.compact"]
    # a loop and the body it runs belong to the loop's scope
    assert sc["while.6"] == sc["gather.2"] == ["engine.join",
                                               "engine.step.search"]
    assert sc["reduce.8"] == ["engine.groupby", None]
    assert "copy.7" not in sc and "p0" not in sc


def test_summary_splits_device_time_and_names_gaps():
    rec = {"ops": {"/device:TPU:0": [
        ["%fusion = s32[8] fusion(s32[8] %a)", 10, 30],
        ["%all-to-all.4 = s32[8] all-to-all(s32[8] %f)", 25, 40],
        ["%while.6 = s32[8] while(s32[8] %a)", 50, 80],
        ["%copy.7 = s32[8] copy(s32[8] %w)", 80, 90]]},
        "spans": [["bench.window", 0, 100], ["bench.submit", 0, 12],
                  ["bench.wait", 12, 100]]}
    spans = [["engine.submit", 0, 11, {"query": 1}],
             ["engine.plan", 2, 5, {"query": 1}],
             ["engine.verify", 40, 60, {"query": 1}]]
    s = scopes.summarize(rec, spans, 1, {"jit_global_fn": scopes.hlo_scopes(
        HLO)})
    assert s["busy_s"] == pytest.approx(70e-9)
    assert s["operator_s"] == pytest.approx({
        "engine.join": 50e-9, "engine.exchange": 15e-9,
        scopes.UNSCOPED: 10e-9})
    assert s["scoped_s"] == pytest.approx(60e-9)
    assert s["step_s"] == pytest.approx({
        "engine.join/engine.step.emit": 20e-9,
        "engine.join/engine.step.search": 30e-9})
    # idle [0,10] within engine.submit, [40,50] within engine.verify,
    # [90,100] under the harness's wait
    assert sorted((n, round(g * 1e9)) for n, g in s["idle_gaps"]) == [
        ("bench.wait", 10), ("engine.submit", 10), ("engine.verify", 10)]
    # the readers of the engine's names, over the summary they are given
    summary = attribute(tracing.summarize(rec, queries=1), rec, spans,
                        {"scopes": {"jit_global_fn": scopes.hlo_scopes(HLO)},
                         "calls": {}, "bytes": {}}, None)
    assert summary.coverage == pytest.approx(60 / 70)
    # the top ops carry their operator and step
    assert dict(summary.device_ops) == pytest.approx({
        "while.6 [other] engine.join/engine.step.search": 30e-9,
        "fusion [other] engine.join/engine.step.emit": 20e-9,
        "all-to-all.4 [alltoall] engine.exchange": 15e-9,
        "copy.7 [other]": 10e-9})
    m = {n: load_module(BENCH / "metrics" / f"{n}.py").read(summary)
         for n in ENGINE_METRICS}
    assert m["join_ms.batch"] == pytest.approx(50e-6)
    assert m["exchange_ms.batch"] == pytest.approx(15e-6)
    assert m["plan_host_ms.batch"] == pytest.approx(3e-6)
    assert m["filter_ms.batch"] is None and m["groupby_ms.batch"] is None
    assert m["dispatch_host_ms.batch"] is None


def test_benchmark_metrics_read_as_recorded():
    """The seven accepted readers give the values they gave when the
    recorded trace was added."""
    rec = json.loads((BENCH / "tests" / "data" / "q1_sf10_tpu_trace.json")
                     .read_text())
    s = tracing.summarize(rec["records"], rec["queries"], rec["categories"])
    want = {"device_idle_frac.batch": 0.0006598661362570146,
            "sort_ms.batch": 1580.041783, "scatter_ms.batch": 3505.513727,
            "gather_ms.batch": 19586.142108, "kernel_ms.batch": None,
            "alltoall_exposed_ms.batch": None,
            "submit_host_ms.batch": 13.47611}
    got = {n: load_module(BENCH / "metrics" / f"{n}.py").read(s)
           for n in want}
    assert got == pytest.approx(want, rel=1e-12)


def test_engine_profile_on_the_cpu(tmp_path):
    from bench import engine_profile as EP

    args = argparse.Namespace(workload="q12_sf40_4chip", seed=2 ** 31 + 7,
                              seconds=0.5, out=str(tmp_path / "w.json.gz"))
    line = EP.profile(args, require_tpu=False,
                      config_override={"scale_factor": 0.04})
    m = {n: line["metrics"][n] for n in ENGINE_METRICS}
    assert all(v is not None and v > 0 for v in m.values()), m
    assert line["coverage"] > 0.9
    assert "engine.join/engine.step.search" in line["step_s"]
    assert line["device"]["count"] == 4
    assert (tmp_path / "w.json.gz").stat().st_size > 0
