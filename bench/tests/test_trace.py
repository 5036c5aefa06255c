"""The trace -> metrics reduction, on a recorded chip trace and on small
hand-made cases."""
import json

import pytest

from bench import tracing
from bench.run import BENCH, load_module


@pytest.fixture(scope="module")
def recorded():
    return json.loads((BENCH / "tests" / "data" / "q1_sf10_tpu_trace.json")
                      .read_text())


def test_union_and_subtract():
    u = tracing.union([(5, 9), (0, 2), (1, 3), (8, 12)], 1, 10)
    assert u == [(1, 3), (5, 10)]
    assert tracing.covered(u) == 7
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert tracing.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_exposed_alltoall_and_gaps():
    rec = {"ops": {"/device:TPU:0": [
        ["%all-to-all.1 = f32[8] all-to-all(f32[8] %a)", 10, 30],
        ["%fusion.2 = f32[8] fusion(f32[8] %b), kind=kLoop", 20, 25],
        ["%sort.3 = (s32[8]) sort(s32[8] %c)", 40, 60]]},
        "spans": [["bench.window", 0, 100], ["bench.submit", 0, 12],
                  ["bench.wait", 12, 100]]}
    s = tracing.summarize(rec, queries=2)
    assert s.busy_s == pytest.approx(40e-9)
    assert s.category_s["alltoall"] == pytest.approx(20e-9)
    assert s.category_s["sort"] == pytest.approx(20e-9)
    assert s.alltoall_exposed_s == pytest.approx(15e-9)
    # idle [0,10] under submit, [30,40] and [60,100] under wait
    assert [(n, round(g * 1e9)) for n, g in s.idle_gaps] == [
        ("bench.wait", 40), ("bench.submit", 10), ("bench.wait", 10)]


def test_hlo_categories_look_into_fusions():
    text = "\n".join([
        "HloModule jit_global_fn, is_scheduled=true",
        "%fused_computation (p0: f32[8], p1: s32[8]) -> f32[8] {",
        "  %p0 = f32[8]{0} parameter(0)",
        "  ROOT %gather.1 = f32[8]{0} gather(f32[8]{0} %p0, s32[8] %p1)",
        "}",
        "%fused_computation.1 (p0: f32[8], p1: s32[8]) -> f32[8] {",
        "  ROOT %scatter.2 = f32[8]{0} scatter(f32[8]{0} %p0, s32[8] %p1),"
        " to_apply=%add",
        "}",
        "ENTRY %main.3 (a: f32[8], i: s32[8]) -> f32[8] {",
        "  %fusion = f32[8]{0} fusion(f32[8]{0} %a, s32[8]{0} %i),"
        " kind=kCustom, calls=%fused_computation",
        "  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %fusion, s32[8]{0} %i),"
        " kind=kCustom, calls=%fused_computation.1",
        "  %custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %a),"
        " custom_call_target=\"tpu_custom_call\"",
        "  ROOT %sort.5 = f32[8]{0} sort(f32[8]{0} %fusion.1)",
        "}"])
    cats = tracing.hlo_categories(text)
    assert cats["fusion"] == "gather"
    assert cats["fusion.1"] == "scatter"
    assert cats["custom-call.4"] == "kernel"
    assert cats["sort.5"] == "sort"


def test_recorded_tpu_trace(recorded):
    rec, cats = recorded["records"], recorded["categories"]
    s = tracing.summarize(rec, recorded["queries"], cats)
    (w,) = [sp for sp in rec["spans"] if sp[0] == "bench.window"]
    assert s.window_s == pytest.approx((w[2] - w[1]) / 1e9)
    ops = rec["ops"]["/device:TPU:0"]
    # busy is the union: at most the sum of the op durations, and only
    # a little below it (async copies overlap the ops that wait on them)
    inside = [(max(a, w[1]), min(b, w[2])) for _, a, b in ops]
    total = sum(b - a for a, b in inside if b > a) / 1e9
    assert 0.999 * total <= s.busy_s <= total
    assert sum(s.category_s.values()) == pytest.approx(s.busy_s, rel=1e-3)
    # the query's 16 row gathers dominate, then the segmented scatters
    assert s.category_s["gather"] > 0.75 * s.busy_s
    assert s.category_s["gather"] > s.category_s["scatter"] \
        > s.category_s["sort"]
    assert s.alltoall_exposed_s == 0
    metrics = {}
    for name in ("device_idle_frac.batch", "sort_ms.batch",
                 "scatter_ms.batch", "gather_ms.batch", "kernel_ms.batch",
                 "alltoall_exposed_ms.batch", "submit_host_ms.batch"):
        metrics[name] = load_module(BENCH / "metrics" / f"{name}.py").read(s)
    assert 0 <= metrics["device_idle_frac.batch"] < 0.01
    assert metrics["gather_ms.batch"] == pytest.approx(
        1e3 * s.category_s["gather"])
    # one chip: no exchange and no kernel to read, so nothing is reported
    assert metrics["kernel_ms.batch"] is None
    assert metrics["alltoall_exposed_ms.batch"] is None
    assert metrics["submit_host_ms.batch"] > 0


def test_every_benchmark_metric_has_a_reader():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert hasattr(load_module(BENCH / "metrics" / f"{m['name']}.py"),
                       "read")


def test_peaks_are_keyed_by_device_kind():
    from bench.run import BenchError, peaks

    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(BenchError):
        peaks("cpu")
