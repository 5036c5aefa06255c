"""From a profiler trace to the engine's own attribution: device time per
operator, host time per engine span.

The engine names its work in two ways (``repro.core``):

- device scopes (``jax.named_scope``) that reach each instruction's
  ``op_name`` metadata in the compiled program: one operator scope per plan
  node (``engine.filter``, ``engine.join``, ...), ``engine.exchange`` around
  every exchange, and ``engine.step.*`` around the row searches and
  reorders inside the local operators;
- host spans (``jax.profiler.TraceAnnotation``) named ``engine.*``, each
  with the ``query`` number that all spans of one query share.

:func:`hlo_scopes` maps each instruction of a compiled module to its
operator and step, beside :func:`bench.tracing.hlo_categories` and from the
same text. :func:`load_spans` reads the engine's host spans from the trace
that :func:`bench.tracing.load` reads. :func:`summarize` reduces both over
the window of a :func:`bench.tracing.load` record, and :func:`metrics`
gives the per-layer numbers by name.

Attribution rule: an instruction belongs to the innermost operator or
exchange scope in its ``op_name``, so an exchange inside a join counts as
exchange and the rest of the join as join. Step scopes refine an
operator's time; they never move it to another. A fusion carries the
metadata of its root instruction, so a fusion that XLA built from the
work of two scopes counts wholly to its root's.
"""
from __future__ import annotations

import collections
import re

from bench import tracing

ENGINE_PREFIX = "engine."
STEP_PREFIX = "engine.step."
OPERATORS = ("engine.filter", "engine.join", "engine.groupby", "engine.sort",
             "engine.window", "engine.setop", "engine.distinct",
             "engine.limit", "engine.exchange")
#: where an instruction or an idle gap has no engine scope or span
UNSCOPED = "unscoped"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"engine\.[\w.]+")

#: per-layer metric -> (operator scope, or host span), read per query
DEVICE_METRICS = {"filter_ms.batch": "engine.filter",
                  "join_ms.batch": "engine.join",
                  "groupby_ms.batch": "engine.groupby",
                  "exchange_ms.batch": "engine.exchange"}
HOST_METRICS = {"plan_host_ms.batch": "engine.plan",
                "dispatch_host_ms.batch": "engine.dispatch"}


def scope_of(op_name: str) -> tuple[str | None, str | None]:
    """(operator, step) of one ``op_name``: the innermost operator or
    exchange scope, and the innermost step scope."""
    op = step = None
    for s in _SCOPE.findall(op_name):
        if s.startswith(STEP_PREFIX):
            step = s
        elif s in OPERATORS:
            op = s
    return op, step


def hlo_scopes(hlo_text: str) -> dict[str, list]:
    """Instruction name -> ``[operator, step]`` over one compiled module's
    text (every computation: the bodies of loops and fusions run as ops of
    their own on the device). Instructions with no ``op_name`` (copies,
    tuples, parameters) are left out."""
    out = {}
    for line in hlo_text.splitlines():
        m = tracing._INSTR.match(line)
        if not m:
            continue
        meta = _OP_NAME.search(m.group(2))
        if meta:
            out[m.group(1)] = list(scope_of(meta.group(1)))
    return out


def load_spans(path: str) -> list:
    """The engine's host spans in a trace: ``[[name, start_ns, end_ns,
    {stat: value}], ...]`` sorted by start; ``path`` as for
    :func:`bench.tracing.load`."""
    import glob
    import os

    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ENGINE_PREFIX):
                    start = int(ev.start_ns)
                    spans.append([ev.name, start,
                                  start + int(ev.duration_ns),
                                  tracing._stats(ev)])
    return sorted(spans, key=lambda r: r[1])


def summarize(records: dict, spans: list, queries: int,
              scopes: dict[str, dict[str, list]]) -> dict:
    """Reduce one traced window (the ``bench.window`` span of ``records``)
    to the engine's attribution.

    ``scopes`` maps a module name to :func:`hlo_scopes` of its compiled
    text. Device seconds per operator are a union per device, then the
    mean over devices, as :func:`bench.tracing.summarize` does categories;
    so are the seconds per step and ``scoped_s``, the union of every
    scoped op. ``span_s`` holds the durations of the
    engine spans inside the window, and ``idle_gaps`` the ten longest gaps
    of the first device, each named by the innermost span (engine or
    harness) that covers it."""
    anywhere = {k: v for sc in scopes.values() for k, v in sc.items()}
    lo, hi = tracing._window(records)
    devices = sorted(records["ops"])
    n = len(devices)
    op_s: dict[str, float] = collections.Counter()
    step_s: dict[str, float] = collections.Counter()
    busy = scoped = 0.0
    gaps = []
    inside = [s for s in spans if s[2] > lo and s[1] < hi]
    named = [s[:3] for s in inside] + [
        s for s in records["spans"] if s[2] > lo and s[1] < hi]
    for dev in devices:
        modules = records.get("modules", {}).get(dev, [])
        by_op = collections.defaultdict(list)
        by_step = collections.defaultdict(list)
        for text, s, e in records["ops"][dev]:
            known = scopes.get(tracing._module_of(modules, s), {}) \
                if modules else anywhere
            op, step = known.get(tracing.op_name(text)) or (None, None)
            by_op[op or UNSCOPED].append((s, e))
            if op and step:
                by_step[f"{op}/{step}"].append((s, e))
        merged = tracing.union((iv for ivs in by_op.values() for iv in ivs),
                               lo, hi)
        busy += tracing.covered(merged)
        scoped += tracing.covered(tracing.union(
            (iv for k, ivs in by_op.items() if k != UNSCOPED for iv in ivs),
            lo, hi))
        for k, ivs in by_op.items():
            op_s[k] += tracing.covered(tracing.union(ivs, lo, hi))
        for k, ivs in by_step.items():
            step_s[k] += tracing.covered(tracing.union(ivs, lo, hi))
        if dev == devices[0]:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append([tracing._covering_span(named, s, e),
                                 (e - s) / 1e9])
    span_s = collections.defaultdict(list)
    for name, s, e, _ in inside:
        span_s[name].append((e - s) / 1e9)
    return {"queries": queries, "window_s": (hi - lo) / 1e9,
            "busy_s": busy / n / 1e9, "scoped_s": scoped / n / 1e9,
            "operator_s": {k: v / n / 1e9 for k, v in sorted(op_s.items())},
            "step_s": {k: v / n / 1e9 for k, v in sorted(step_s.items())},
            "span_s": dict(span_s),
            "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:10]}


def metrics(summary: dict) -> dict[str, float | None]:
    """The per-layer numbers by name, in ms per query; None where the
    scope ran nothing or the span never opened."""
    q = summary["queries"]
    out = {}
    for name, scope in DEVICE_METRICS.items():
        s = summary["operator_s"].get(scope, 0.0)
        out[name] = 1e3 * s / q if s and q else None
    for name, span in HOST_METRICS.items():
        s = sum(summary["span_s"].get(span, ()))
        out[name] = 1e3 * s / q if s and q else None
    return out


def coverage(summary: dict) -> float:
    """Share of the busy device time in which an op of some operator or
    exchange scope ran."""
    return summary["scoped_s"] / summary["busy_s"] if summary["busy_s"] \
        else 0.0
