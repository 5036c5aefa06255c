"""Smoke run of the engine's main path on a TPU, at a real data size.

Builds a fact table (``orders``, 2^25 rows per chip) and a dimension table
(``dims``, 2^20 rows) from ``--seed``, registers both through
``ServingSession.register(..., analyze=True)`` and runs five queries
through ``LazyFrame`` / ``collect()``: the fused ``shard_map`` plan with
its Pallas kernels. Every result is fetched to the host and compared
exactly with a vectorised NumPy reference (all float inputs are
integer-valued, so every f32 sum is exact in any order). A short async
open loop then re-runs queries 1-4 warm. The last line of standard output
is the JSON verdict; every earlier line is a smoke timing or a count, not
a benchmark number.

    python chip_smoke.py               # one chip, all phases
    python chip_smoke.py --chips 4     # four chips: the shuffle-bearing
                                       # queries 2-5 over 4 x 2^25 rows
    JAX_PLATFORMS=cpu python chip_smoke.py --rows-log2 12
                                       # rehearsal at a tiny size; exits
                                       # non-zero, since no TPU ran it

Without a TPU the run fails, and prints no verdict. The compile cache goes
where ``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/`` in the
checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

K_RANGE = 1 << 20   # distinct join/group keys in orders.k, rows of dims
G_RANGE = 64        # distinct values of orders.g


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


# -- data and references ------------------------------------------------------

def make_data(rows: int, seed: int):
    rng = np.random.default_rng(seed)
    orders = {
        "k": rng.integers(0, K_RANGE, rows, dtype=np.int32),
        "g": rng.integers(0, G_RANGE, rows, dtype=np.int32),
        "d0": rng.integers(-8, 9, rows, dtype=np.int32).astype(np.float32),
        # unique, so every (k, d1) order below is total
        "d1": rng.permutation(rows).astype(np.int32),
    }
    dims = {"k": np.arange(K_RANGE, dtype=np.int32),
            "w": rng.integers(0, 9, K_RANGE, dtype=np.int32)
            .astype(np.float32)}
    return orders, dims


def ref_groupby_g(o):
    cnt = np.bincount(o["g"], minlength=G_RANGE)
    s = np.bincount(o["g"], weights=o["d0"], minlength=G_RANGE)
    keys = np.flatnonzero(cnt)
    s = s[keys].astype(np.float32)
    c = cnt[keys].astype(np.int32)
    return {"g": keys.astype(np.int32), "d0_sum": s, "d0_count": c,
            "d0_mean": s / c.astype(np.float32)}


def ref_groupby_k(o):
    keys = np.flatnonzero(np.bincount(o["k"], minlength=K_RANGE))
    s = np.bincount(o["k"], weights=o["d0"], minlength=K_RANGE)
    return {"k": keys.astype(np.int32), "d0_sum": s[keys].astype(np.float32)}


def ref_sort_limit(o, n=100):
    # a stable sort's first n rows: the rows whose key is at most the n-th
    # smallest, stably sorted (the sort is global and stable in the engine)
    k = o["k"]
    cand = np.flatnonzero(k <= np.partition(k, n - 1)[n - 1])
    idx = cand[np.argsort(k[cand], kind="stable")][:n]
    return {c: v[idx] for c, v in o.items()}


def ref_join_groupby(o, dims):
    cnt = np.bincount(o["g"], minlength=G_RANGE)
    s = np.bincount(o["g"], weights=dims["w"][o["k"]], minlength=G_RANGE)
    keys = np.flatnonzero(cnt)
    return {"g": keys.astype(np.int32), "w_sum": s[keys].astype(np.float32)}


def ref_window(o):
    # d1 is unique, so (k, d1) packs into one unique int64 sort key
    order = np.argsort(o["k"].astype(np.int64) * len(o["k"]) + o["d1"])
    out = {c: v[order] for c, v in o.items()}
    k = out["k"]
    n = len(k)
    start = np.ones(n, bool)
    start[1:] = k[1:] != k[:-1]
    first = np.maximum.accumulate(np.where(start, np.arange(n), 0))
    out["rank"] = (np.arange(n) - first + 1).astype(np.int32)
    cs = np.cumsum(out["d0"].astype(np.float64))
    before = np.where(first > 0, cs[first - 1], 0.0)
    out["d0_cumsum"] = (cs - before).astype(np.float32)
    return out


# -- the run ------------------------------------------------------------------

def to_host(t) -> dict[str, np.ndarray]:
    """Valid rows of a DistTable on the host, in shard order."""
    p, c = t.num_shards, t.local_capacity
    counts = np.asarray(t.row_counts)
    out = {}
    for name, col in t.columns.items():
        a = np.asarray(col).reshape((p, c) + col.shape[1:])
        out[name] = np.concatenate([a[i, :counts[i]] for i in range(p)])
    return out


#: columns computed by an f32 division, and the units in the last place
#: they may differ by: the TPU divides through a refined reciprocal, which
#: is not correctly rounded as NumPy's division is. Every other column is
#: compared exactly.
DIVIDED = {"d0_mean": 2}


def compare(name: str, got: dict, want: dict, sort_by: str | None = None):
    if sort_by is not None:  # unique keys: output order is per shard
        got = {c: v[np.argsort(got[sort_by], kind="stable")]
               for c, v in got.items()}
    for col, w in want.items():
        check(col in got, f"{name}: no column {col!r} in {sorted(got)}")
        g = got[col]
        check(g.shape == w.shape, f"{name}.{col}: shape {g.shape} != "
              f"{w.shape}")
        check(g.dtype == w.dtype, f"{name}.{col}: dtype {g.dtype} != "
              f"{w.dtype}")
        if col in DIVIDED:
            ulps = np.abs(g - w) / np.spacing(np.abs(w))
            print(f"{name}.{col}: at most {ulps.max()} ulp from NumPy",
                  flush=True)
            bad = np.flatnonzero(~(ulps <= DIVIDED[col]))
        else:
            bad = np.flatnonzero(g != w)
        check(bad.size == 0, f"{name}.{col}: {bad.size} rows differ, first "
              f"at {bad[:1].tolist()}: {g[bad[:3]].tolist()} != "
              f"{w[bad[:3]].tolist()}")


def queries(chips: int):
    """(label, builder, reference key) in run order; the single-chip run
    has all five, the four-chip run the shuffle-bearing 2-5."""
    qs = [
        ("q1_groupby_g", lambda s: s.frame("orders").groupby(
            "g", [("d0", "sum"), ("d0", "count"), ("d0", "mean")]), "g"),
        ("q2_groupby_k", lambda s: s.frame("orders").groupby(
            "k", [("d0", "sum")]), "k"),
        ("q3_sort_limit", lambda s: s.frame("orders").sort("k").limit(100),
         None),
        ("q4_join_groupby", lambda s: s.frame("orders").join(
            s.frame("dims"), "k").groupby("g", [("w", "sum")]), "g"),
        ("q5_sort_window", lambda s: s.frame("orders").sort(["k", "d1"])
         .window("k", ["rank", ("cumsum", "d0")], order_by="d1"), None),
    ]
    return qs if chips == 1 else qs[1:]


def timed_collect(sess, build):
    """(wall seconds, result) of one query, run to completion."""
    import jax

    t0 = time.perf_counter()
    out = build(sess).collect()
    jax.block_until_ready(out.columns)
    return time.perf_counter() - t0, out


def one_chip_phases(sess, qs, refs, cache, on_tpu: bool):
    """Each query cold and warm, then the async open loop over 1-4."""
    ctx = sess.ctx
    saw_kernel = False
    for label, build, key in qs:
        for phase in ("cold", "warm"):
            misses = ctx.cache_stats()["misses"]
            secs, out = timed_collect(sess, build)
            got = to_host(out)
            compiles = ctx.cache_stats()["misses"] - misses
            print(f"{label} {phase}: {secs:.6f} s wall, {compiles} "
                  f"compiles, {sum(v.size for v in got.values())} cells "
                  "to host", flush=True)
            compare(f"{label} ({phase})", got, refs[label], key)
            if phase == "cold" and not saw_kernel and cache.last is not None:
                # the query's executable, lowered again (cheap); compiled
                # again (a persistent-cache hit) only where it calls a kernel
                tabs = build(sess)._inputs
                lowered = cache.last.lower(*[(t.columns, t.row_counts)
                                             for t in tabs])
                if "tpu_custom_call" in lowered.as_text():
                    saw_kernel = "tpu_custom_call" in \
                        lowered.compile().as_text()
                print(f"{label}: compiled text holds tpu_custom_call: "
                      f"{saw_kernel}", flush=True)
    if on_tpu:
        check(saw_kernel, "no compiled query holds a tpu_custom_call")

    workload = [(label, build) for label, build, _ in qs[:4]]
    keys = {label: key for label, _, key in qs}
    report, results = sess.run_open_loop(
        workload, num_clients=2, queries_per_client=2, mode="async")
    print(f"open loop: {report.summary()}", flush=True)
    check(not report.errors, f"open-loop errors: {report.errors}")
    check(report.compiles == 0,
          f"{report.compiles} compiles on the warm open loop")
    for label, out in zip(report.shapes, results):
        compare(f"{label} (open loop)", to_host(out), refs[label],
                keys[label])


def four_chip_phase(sess, qs, refs):
    """One pass per query, submitted from one client thread each: XLA
    compiles outside the interpreter lock, so the four cold compiles
    overlap. What this path checks is the shuffle across chips."""
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(len(qs)) as pool:
        runs = [pool.submit(timed_collect, sess, build) for _, build, _ in qs]
    for (label, _, key), run in zip(qs, runs):
        secs, out = run.result()
        got = to_host(out)
        print(f"{label} cold (concurrent): {secs:.6f} s wall, "
              f"{sum(v.size for v in got.values())} cells to host",
              flush=True)
        compare(label, got, refs[label], key)


def run(args) -> dict:
    import jax

    from repro.core.context import DistContext
    from repro.core.plan_cache import PlanCache
    from repro.core.serving import ServingSession
    from repro.core.table import Table
    from repro.utils import interpret_mode, use_compile_cache

    use_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    on_tpu = dev.platform == "tpu"
    check(on_tpu or args.rows_log2 is not None,
          f"no TPU (platform {dev.platform}); a CPU rehearsal needs "
          "--rows-log2")
    check(len(devs) == args.chips, f"{args.chips} chips asked, "
          f"{len(devs)} found")
    if on_tpu:
        check(not interpret_mode(), "Pallas kernels in interpret mode")

    rows = args.chips << (args.rows_log2 or 25)
    t0 = time.perf_counter()
    orders, dims = make_data(rows, args.seed)
    refs = {"q1_groupby_g": ref_groupby_g(orders),
            "q2_groupby_k": ref_groupby_k(orders),
            "q3_sort_limit": ref_sort_limit(orders),
            "q4_join_groupby": ref_join_groupby(orders, dims),
            "q5_sort_window": ref_window(orders)}
    print(f"data + references: {rows} orders rows, {K_RANGE} dims rows, "
          f"{time.perf_counter() - t0:.3f} s", flush=True)

    class RecordingCache(PlanCache):
        """Keeps the last executable admitted, to read its compiled text."""
        last = None

        def put(self, key, value, **kw):
            self.last = value
            return super().put(key, value, **kw)

    cache = RecordingCache()
    ctx = DistContext(plan_cache=cache)  # default mesh: every device
    sess = ServingSession(ctx, max_in_flight=8)
    t0 = time.perf_counter()
    sess.register("orders", Table.from_arrays(orders), analyze=True)
    sess.register("dims", Table.from_arrays(dims), analyze=True)
    print(f"register + analyze: {time.perf_counter() - t0:.3f} s",
          flush=True)
    spread = len(sess.table("orders").columns["k"].sharding.device_set)
    check(spread == args.chips,
          f"orders.k spans {spread} devices, not {args.chips}")

    qs = queries(args.chips)
    if args.chips == 1:
        one_chip_phases(sess, qs, refs, cache, on_tpu)
    else:
        four_chip_phase(sess, qs, refs)

    stats = ctx.cache_stats()
    print(f"compiles: {stats['misses']}, plan-cache hits: {stats['hits']}, "
          f"overflow retries: {stats['overflow_retries']}", flush=True)
    for counter in ("degraded_kernel", "degraded_shuffle", "quarantines",
                    "failed_queries"):
        check(stats[counter] == 0, f"{counter} = {stats[counter]}")
    mem = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use', 'not reported')}",
          flush=True)
    check(on_tpu, f"rehearsal on {dev.platform} passed; no TPU ran it")
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind,
                                   "count": len(devs)}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20200715)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rows-log2", type=int, default=None,
                    help="orders rows per chip as a power of two "
                         "(default 25); set it to rehearse off the chip")
    args = ap.parse_args()
    try:
        verdict = run(args)
    except Exception:  # noqa: BLE001 — every failure ends the run
        traceback.print_exc()
        print("chip smoke FAILED", file=sys.stderr)
        return 1
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
