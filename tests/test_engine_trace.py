"""The engine's own trace names: operator scopes in the compiled program
and per-query host spans in the profiler trace."""
import re

import jax
import numpy as np
import pytest

from repro.core.context import DistContext
from repro.core.table import Table
from repro.testing.spans import engine_spans

OPERATORS = ("engine.filter", "engine.join", "engine.groupby", "engine.sort",
             "engine.window", "engine.setop", "engine.distinct",
             "engine.limit", "engine.exchange")
_COMP = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: what does the device work: an op running one of these, itself or in
#: the computation it fuses
WORK = {"gather", "sort", "scatter", "while", "custom-call"}


def _entry_work(text: str) -> list[tuple[str, set, str]]:
    """(name, work opcodes, op_name) of each entry-level instruction that
    does row work."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m and not line.startswith("HloModule"):
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            comps[cur].append(m.groups())

    def runs(rest: str) -> set:
        m = _OPCODE.search(" " + rest)
        ops = {m.group(1)} if m else set()
        for c in _CALLS.findall(rest):
            for _, r in comps.get(c, ()):
                ops |= runs(r)
        return ops

    out = []
    for name, rest in comps[entry]:
        work = runs(rest) & WORK
        if work:
            meta = _OP_NAME.search(rest)
            out.append((name, work, meta.group(1) if meta else ""))
    return out


def _scope(op_name: str) -> str | None:
    found = [s for s in re.findall(r"engine\.[\w.]+", op_name)
             if s in OPERATORS]
    return found[-1] if found else None


def _tables(ctx, right_rows=50):
    rng = np.random.default_rng(3)
    a = Table.from_arrays({
        "k": rng.integers(0, 50, 300).astype(np.int32),
        "x": rng.random(300).astype(np.float32)})
    b = Table.from_arrays({"k": np.arange(right_rows, dtype=np.int32),
                           "g": (np.arange(right_rows) % 3).astype(np.int32)})
    return ctx.scatter(a), ctx.scatter(b)


def _query(ctx, da, db, out_capacity=None):
    return (ctx.frame(da).select(lambda c: c["x"] > 0.3, key="x>0.3")
            .join(ctx.frame(db), on="k", out_capacity=out_capacity)
            .groupby("g", (("x", "sum"),)))


#: the join's row searches take the merge and the scatter-max at like-sized
#: sides; 300 probe rows into 50,000 (output capacity 8) keep all three
#: scan searches
SEARCH_SHAPES = {"merge": {}, "scan": {"right_rows": 50_000,
                                       "out_capacity": 8}}


@pytest.mark.parametrize("search", sorted(SEARCH_SHAPES))
def test_compiled_plan_work_carries_operator_scopes(search):
    shape = dict(SEARCH_SHAPES[search])
    out_capacity = shape.pop("out_capacity", None)
    ctx = DistContext()
    da, db = _tables(ctx, **shape)
    fr = _query(ctx, da, db, out_capacity)
    fr.collect()
    args = tuple((t.columns, t.row_counts) for t in fr._inputs)
    (key,) = ctx.plan_cache.keys()
    text = ctx.plan_cache.get(key).lower(*args).compile().as_text()
    work = _entry_work(text)
    assert work
    unscoped = [(n, w) for n, w, op in work if _scope(op) is None]
    assert not unscoped, unscoped
    scopes = {_scope(op) for _, _, op in work}
    assert {"engine.filter", "engine.join", "engine.groupby"} <= scopes
    loops = [op for _, w, op in work if "while" in w
             and "searchsorted" in op]
    passes = [(w, op) for _, w, op in work
              if "merge_search" in op or "expand_slots" in op]
    if search == "scan":
        # a few probe rows into many: the three searchsorted loops
        assert len(loops) == 3, loops
        assert not passes, passes
        searches = loops
    else:
        # start / end from one merge (two sorts and scans), the slot
        # expansion from one scatter-max and a running max: no loop
        assert not loops, loops
        assert sum("sort" in w for w, op in passes
                   if "merge_search" in op) == 2, passes
        assert any("scatter" in w for w, op in passes
                   if "expand_slots" in op), passes
        assert not any("while" in w for w, _ in passes), passes
        searches = [op for _, op in passes]
    assert all(_scope(op) == "engine.join" and "engine.step.search" in op
               for op in searches), searches


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_host_spans_name_each_query(tmp_path):
    ctx = DistContext()
    da, db = _tables(ctx)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            jax.block_until_ready(
                _query(ctx, da, db).collect_async().result().columns)
    finally:
        jax.profiler.stop_trace()
    spans = engine_spans(str(tmp_path))
    submits = [s for s in spans if s[0] == "engine.submit"]
    assert len(submits) == 2
    first, second = (s[3]["query"] for s in submits)
    assert first != second
    for sub, kind in zip(submits, ("engine.compile", "engine.dispatch")):
        q = sub[3]["query"]
        mine = {s[0]: s for s in spans if s[3].get("query") == q}
        assert set(mine) == {"engine.submit", "engine.plan", kind,
                             "engine.verify"}, sorted(mine)
        assert _inside(mine["engine.plan"], sub)
        assert _inside(mine[kind], sub)
        assert mine["engine.plan"][2] <= mine[kind][1]
        assert mine[kind][3]["cache"] == "plan"
        assert mine["engine.verify"][1] >= sub[2]
    assert ctx.cache_stats()["compile_s"] > 0


def test_compile_seconds_count_only_misses():
    ctx = DistContext()
    da, db = _tables(ctx)
    _query(ctx, da, db).collect()
    cold = ctx.cache_stats()["compile_s"]
    assert cold > 0
    _query(ctx, da, db).collect()
    assert ctx.cache_stats()["compile_s"] == cold
    assert ctx.cache_stats()["misses"] == 1


def test_retry_rung_gets_a_span(tmp_path):
    from repro.core import faults as FLT

    ctx = DistContext(faults=[FLT.FaultPlan("kernel.dispatch", nth=1)])
    t = Table.from_arrays({"k": (np.arange(64) % 5).astype(np.int32),
                           "d0": np.ones(64, np.float32)})
    jax.profiler.start_trace(str(tmp_path))
    try:
        out, _ = ctx.groupby(ctx.scatter(t), "k", (("d0", "sum"),),
                             strategy="shuffle")
    finally:
        jax.profiler.stop_trace()
    assert ctx.cache_stats()["degraded_kernel"] == 1
    retries = [s for s in engine_spans(str(tmp_path))
               if s[0] == "engine.retry"]
    assert [s[3]["rung"] for s in retries] == ["oracle-kernel"]
