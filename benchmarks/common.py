"""Shared benchmark utilities: timing, CSV emission, CPU worker processes."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np


def run_cpu_worker(argv: list[str], devices: int) -> dict:
    """Run ``python <argv>`` on ``devices`` virtual CPU devices and return
    the JSON of its last ``RESULT:`` line.

    These workers are CPU runs by construction: the device count must be
    fixed before JAX starts, and ``JAX_PLATFORMS=cpu`` keeps them off any
    accelerator the host has (a chip serves one process at a time)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src:" + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, *argv], capture_output=True,
                         text=True, env=env, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT:")][-1]
    return json.loads(line[7:])


def device_record() -> dict:
    """The devices a worker ran on, as JAX reports them."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": jax.device_count()}


def timeit(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall-clock seconds of fn(*args) (block_until_ready'd)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def timeit_host(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    for _ in range(warmup):
        fn(*args)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


class Table:
    def __init__(self, title: str, columns: list[str]):
        self.title = title
        self.columns = columns
        self.rows: list[list] = []

    def add(self, *row):
        self.rows.append(list(row))

    def emit(self):
        print(f"\n## {self.title}")
        print(",".join(self.columns))
        for r in self.rows:
            print(",".join(
                f"{v:.4g}" if isinstance(v, float) else str(v) for v in r))

    def to_dict(self) -> dict:
        """Machine-readable form for the --json trajectory output."""
        return {"title": self.title, "columns": self.columns,
                "rows": self.rows}
