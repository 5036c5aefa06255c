"""A whole run of each cell on the CPU at a small scale factor: the
engine agrees with the reference, and a broken timed path, or the
control in the program's place, comes out not correct."""
import functools
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import calibrate as CAL
from bench import run as R

SMALL = {"q1_sf10": {"scale_factor": 0.01},
         "q12_sf40_4chip": {"scale_factor": 0.04}}

#: cells whose files are kept while they wait outside ``BENCHMARK.json``
#: for a program fix (PERF.md, Open questions)
WAITING = {"configs": [{"name": "tpch_sf10",
                        "file": "bench/configs/tpch_sf10.json"}],
           "workloads": [{"name": "q1_sf10", "config": "tpch_sf10",
                          "traffic": "q1_closed", "chips": 1}]}


@pytest.fixture(autouse=True)
def waiting_cells(tmp_path_factory, monkeypatch):
    """``load_cell`` finds the waiting cells beside the benchmark's own."""
    root = tmp_path_factory.mktemp("spec")
    spec = json.loads((R.ROOT / "BENCHMARK.json").read_text())
    for k, extra in WAITING.items():
        spec[k] = spec[k] + extra
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench").symlink_to(R.BENCH)
    monkeypatch.setattr(R, "load_cell",
                        functools.partial(R.load_cell, root=root))


def _execute(cell, seed=2 ** 31 + 7, trace=0, run=R):
    args = run.parse_args(["--workload", cell, "--seed", str(seed),
                           "--seconds", "0.5", "--trace", str(trace)])
    return run.execute(args, require_tpu=False, config_override=SMALL[cell])


@pytest.mark.parametrize("cell", sorted(SMALL))
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_correct(cell, trace):
    line = _execute(cell, trace=trace)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert list(line)[-1] == "checks"
    spec = R.load_cell(cell)
    want = spec["per_layer" if trace else "end_to_end"]
    if trace:
        assert set(line["metrics"]) <= {m["name"] for m in want}
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert line["breakdown"]["device_ops"]
    else:
        assert set(line["metrics"]) == {m["name"] for m in want}
        assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == 4


#: per-layer metrics read from the engine's scopes and spans and from the
#: kernels' bytes and seconds, each with something to read on the CPU
ATTRIBUTED = ("filter_ms.batch", "join_ms.batch", "groupby_ms.batch",
              "exchange_ms.batch", "plan_host_ms.batch",
              "dispatch_host_ms.batch", "hash32_roofline",
              "bucket_histogram_roofline", "segment_reduce_roofline")


def _check_shares(metrics):
    for n, m in metrics.items():
        if n.endswith("_roofline"):
            assert m["unit"] == "%" and 0 < m["value"] <= 100, (n, m)


def _v5e_peaks(run, monkeypatch):
    """The readers divide by a v5e's HBM bandwidth, as on the chip (off
    the chip a run knows no peak): such a share is no device reading."""
    attribute = run.attribute
    bw = run.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    monkeypatch.setattr(run, "attribute",
                        lambda *a: attribute(*a[:-1], bw))


def test_trace_run_reads_the_engines_names(monkeypatch):
    _v5e_peaks(R, monkeypatch)
    line = _execute("q12_sf40_4chip", trace=1)
    assert line["correct"], line["checks"]
    got = line["metrics"]
    missing = [n for n in ATTRIBUTED if n not in got]
    assert not missing, got
    assert all(got[n]["value"] > 0 for n in ATTRIBUTED), got
    _check_shares(got)


#: what a later cell brings: its own readers, each one file, over the
#: engine's scopes or a kernel's roofline
NEW_READERS = {
    "groupby_ms.q1": (
        "def read(summary):\n"
        "    s = summary.operator_s.get('engine.groupby')\n"
        "    return 1e3 * s / summary.queries if s else None\n"),
    "segment_reduce_roofline.q1": (
        "from bench import roofline\n\n\n"
        "def read(summary):\n"
        "    return roofline.share(summary, '_segment_reduce_jit')\n"),
}


def test_new_cell_takes_metrics_with_one_file_and_one_entry(tmp_path,
                                                           monkeypatch):
    """A copy of the benchmark gains the waiting ``q1_sf10`` cell and two
    per-layer metrics by new files and appended entries alone, and its
    traced run reads both."""
    shutil.copy(R.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(R.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for k, extra in WAITING.items():
        spec[k] = spec[k] + extra
    for name, source in NEW_READERS.items():
        (tmp_path / "bench" / "metrics" / f"{name}.py").write_text(source)
        spec["per_layer"].append({
            "name": name, "unit": "%" if "roofline" in name else "ms",
            "better": "higher" if "roofline" in name else "lower",
            "source": "device_trace", "moves": "rows_per_s",
            "layer": "kernels" if "roofline" in name else "local operators",
            "workloads": ["q1_sf10"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    before = json.loads((R.ROOT / "BENCHMARK.json").read_text())
    assert all(spec[k][:len(v)] == v for k, v in before.items()
               if isinstance(v, list))
    copy = R.load_module(tmp_path / "bench" / "run.py")
    _v5e_peaks(copy, monkeypatch)
    line = _execute("q1_sf10", trace=1, run=copy)
    assert line["correct"], line["checks"]
    got = line["metrics"]
    assert set(NEW_READERS) <= set(got), got
    assert all(got[n]["value"] > 0 for n in NEW_READERS)
    _check_shares(got)


def _keep_half(select):
    def broken(table, predicate):
        out = select(table, predicate)
        return out.__class__(out.columns, out.row_count // 2)
    return broken


def _alter_answer(finalize):
    def broken(partial, keys, pairs):
        out = finalize(partial, keys, pairs)
        cols = dict(out.columns)
        # one count off by one: the answers compared exactly
        name = next(k for k in sorted(cols) if k not in keys
                    and np.issubdtype(cols[k].dtype, np.integer))
        cols[name] = cols[name].at[0].add(1)
        return out.__class__(cols, out.row_count)
    return broken


def _skip_exchange(shuffle):
    def broken(table, keys, **kw):
        return shuffle(table, keys, **dict(kw, skip=True))
    return broken


def _skip_join_exchange(shuffle):
    def broken(table, keys, **kw):
        skip = kw.get("skip", False) or kw.get("label", "").startswith(
            "join.")
        return shuffle(table, keys, **dict(kw, skip=skip))
    return broken


FAULTS = {
    "half the rows left out": ("repro.core.ops_local", "select",
                               _keep_half),
    "an answer altered": ("repro.core.ops_agg", "_finalize", _alter_answer),
    "every exchange left out": ("repro.core.ops_dist", "_shuffle",
                                _skip_exchange),
    "the join's exchange left out": ("repro.core.ops_dist", "_shuffle",
                                     _skip_join_exchange),
}
#: the faults each cell's timed path can have (one chip has no exchange,
#: Q1 no join)
CAN_HAVE = {"q1_sf10": ("half the rows left out", "an answer altered"),
            "q12_sf40_4chip": tuple(FAULTS)}


@pytest.mark.parametrize("cell,fault", [(c, f) for c in sorted(CAN_HAVE)
                                        for f in CAN_HAVE[c]])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    module, attr, breaker = FAULTS[fault]
    mod = __import__(module, fromlist=[attr])
    monkeypatch.setattr(mod, attr, breaker(getattr(mod, attr)))
    line = _execute(cell)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_control_is_not_correct(cell):
    r = CAL.calibrate(cell, 11, 1, require_tpu=False,
                      config_override=SMALL[cell])
    limits = dict(R.load_cell(cell)["mix"]["limits"])
    ok = lambda nums: all(nums[k] <= v for k, v in limits.items())  # noqa
    assert ok(r["program"]), r
    assert not ok(r["control"]), r


def test_no_tpu_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(R.BENCH / "run.py"),
                        "--workload", "q12_sf40_4chip", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_bare_benchmark_files_give_no_result(tmp_path):
    shutil.copy(R.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(R.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "q12_sf40_4chip", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def test_benchmark_file_follows_its_rules():
    spec = json.loads((R.ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in spec["workloads"]}
    for w in spec["workloads"]:
        assert (R.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for c in spec["configs"]:
        cfg = json.loads((R.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and set(c["reduced"]) <= set(cfg)
    for m in spec["per_layer"]:
        assert set(m["workloads"]) <= cells
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in spec[k]]
    assert len(names) == len(set(names))
    assert all(len(w["why"]) <= 200 for w in spec["workloads"])
