"""From a profiler trace to the benchmark's per-layer numbers.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain records: the operations that ran on each device and the harness's
own host spans (``bench.*``). :func:`summarize` reduces those records over
the traced window: busy time as the union of operation intervals, device
time per operation category, all-to-all time during which nothing else ran
on that device, and the idle gaps named by the host span that covers each.
The per-layer readers under ``bench/metrics/`` read the summary.

The records are plain JSON-able lists, so a trace cut down to a few
hundred events can be kept as a test fixture (``bench/tests/data``).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

#: HLO opcodes, tried in order, that put a device op in a category; an
#: op is classified by every opcode it runs, its fused computation's too
CATEGORIES = (
    ("alltoall", ("all-to-all", "all-to-all-start", "all-to-all-done")),
    ("kernel", ("tpu_custom_call",)),
    ("scatter", ("scatter",)),
    ("gather", ("gather",)),
    ("sort", ("sort",)),
)

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)="
                    r"\{?%?([\w.\-]+)")
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")


def _opcodes(rest: str) -> set[str]:
    m = _OPCODE.search(" " + rest)
    ops = {m.group(1)} if m else set()
    if "tpu_custom_call" in rest:
        ops.add("tpu_custom_call")
    return ops


def _classify(ops) -> str:
    for cat, names in CATEGORIES:
        if any(o in ops for o in names):
            return cat
    return "other"


def op_name(text: str) -> str:
    """The instruction name of a device op's event (``%fusion.4 = ...``
    gives ``fusion.4``)."""
    m = _INSTR.match(text)
    return m.group(1) if m else text


def category(text: str) -> str:
    """Category of a device op from its own HLO text alone, or from its
    name (``sort.35``) where the trace gives only that."""
    m = _INSTR.match(text)
    return _classify(_opcodes(m.group(2)) if m
                     else {text.split(".")[0]})


def hlo_categories(hlo_text: str) -> dict[str, str]:
    """Instruction name -> category, over one compiled module's text,
    looking through fusions, calls and loops into what they run."""
    comps: dict[str, list] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and cur is not None:
            name, rest = m.groups()
            comps[cur].append((name, _opcodes(rest), _CALLS.findall(rest)))
            continue
        m = _COMP.match(line)
        if m and not line.startswith("HloModule"):
            cur = m.group(1)
            comps[cur] = []
    memo: dict[str, set] = {}

    def runs(comp: str) -> set:
        if comp not in memo:
            memo[comp] = set()
            ops = set()
            for _, own, calls in comps.get(comp, ()):
                ops |= own
                for c in calls:
                    ops |= runs(c)
            memo[comp] = ops
        return memo[comp]

    out = {}
    for instrs in comps.values():
        for name, own, calls in instrs:
            ops = set(own)
            for c in calls:
                ops |= runs(c)
            out[name] = _classify(ops)
    return out


def _stats(event) -> dict:
    import warnings

    with warnings.catch_warnings():
        # jaxlib's event_stats type warns on iteration under Python 3.12
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(event.stats)


def load(path: str) -> dict:
    """Records of one trace: ``{"ops": {device: [[op, start_ns, end_ns],
    ...]}, "modules": {device: [[module, start_ns, end_ns], ...]},
    "spans": [[name, start_ns, end_ns], ...]}``.

    ``path`` is an ``.xplane.pb`` file or a directory holding one. Device
    ops come from each ``/device:`` plane's ``XLA Ops`` line; where the
    backend runs its ops on host threads (the CPU), from the events that
    carry an ``hlo_op`` stat, keyed by their ``device_ordinal``.
    """
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    ops: dict[str, list] = collections.defaultdict(list)
    modules: dict[str, list] = collections.defaultdict(list)
    spans: list = []
    for plane in data.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                start = int(ev.start_ns)
                end = start + int(ev.duration_ns)
                if on_device:
                    if line.name == "XLA Ops":
                        ops[plane.name].append([ev.name, start, end])
                    elif line.name == "XLA Modules":
                        # "jit_global_fn(1538...)": the name without its id
                        modules[plane.name].append(
                            [ev.name.split("(")[0], start, end])
                    continue
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append([ev.name, start, end])
                    continue
                st = _stats(ev)
                if "hlo_op" in st and "device_ordinal" in st:
                    ops[f"/device:CPU:{st['device_ordinal']}"].append(
                        [ev.name, start, end])
    return {"ops": {d: sorted(v, key=lambda r: r[1])
                    for d, v in sorted(ops.items())},
            "modules": {d: sorted(v, key=lambda r: r[1])
                        for d, v in sorted(modules.items())},
            "spans": sorted(spans, key=lambda r: r[1])}


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged, sorted intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of merged intervals ``a`` that no merged interval of ``b``
    covers."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Summary:
    """The traced window reduced to what the per-layer readers need."""

    window_s: float
    queries: int                       # queries completed in the window
    devices: int
    busy_s: float                      # mean over devices
    category_s: dict[str, float]       # mean over devices
    alltoall_exposed_s: float          # mean over devices
    span_s: dict[str, list[float]]     # harness span name -> durations
    device_ops: list                   # [[op name, seconds]], top 10
    idle_gaps: list                    # [[host span, seconds]], top 10


def _window(records: dict) -> tuple[int, int]:
    w = [s for s in records["spans"] if s[0] == WINDOW_SPAN]
    if len(w) != 1:
        raise ValueError(f"{len(w)} {WINDOW_SPAN} spans in the trace")
    return w[0][1], w[0][2]


def _covering_span(spans, s: float, e: float) -> str:
    """The innermost harness span overlapping [s, e] most, or ``host``."""
    best, best_key = "host", (0.0, 0.0)
    for name, a, b in spans:
        if name == WINDOW_SPAN:
            continue
        ov = min(b, e) - max(a, s)
        if ov > 0 and (ov, -(b - a)) > best_key:
            best, best_key = name, (ov, -(b - a))
    return best


def _module_of(modules, t: float) -> str | None:
    """Name of the module whose span holds time ``t`` on one device."""
    starts = [m[1] for m in modules]
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][1] <= t <= modules[i][2]:
        return modules[i][0]
    return None


def summarize(records: dict, queries: int,
              categories: dict[str, dict[str, str]] | None = None
              ) -> Summary:
    """Reduce a trace's records over its ``bench.window`` span.

    ``categories`` maps a module name to :func:`hlo_categories` of its
    compiled text; an op of another module is classified by its own HLO
    text alone."""
    categories = categories or {}
    # a backend that records no module spans (the CPU) names its ops
    # uniquely within the one module the window runs
    anywhere = {k: v for cats in categories.values() for k, v in cats.items()}
    lo, hi = _window(records)
    window = hi - lo
    devices = sorted(records["ops"])
    if not devices:
        raise ValueError("no device operations in the trace")
    n = len(devices)
    busy = 0.0
    cats: dict[str, float] = collections.Counter()
    exposed = 0.0
    per_op: dict[str, float] = collections.Counter()
    gaps: list = []
    spans = [s for s in records["spans"] if s[2] > lo and s[1] < hi]
    for dev in devices:
        evs = records["ops"][dev]
        modules = records.get("modules", {}).get(dev, [])
        merged = union(((s, e) for _, s, e in evs), lo, hi)
        busy += covered(merged)
        by_cat = collections.defaultdict(list)
        for text, s, e in evs:
            name = op_name(text)
            known = categories.get(_module_of(modules, s), {}) if modules \
                else anywhere
            cat = known.get(name) or category(text)
            by_cat[cat].append((s, e))
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[f"{name} [{cat}]"] += d
        for cat, ivs in by_cat.items():
            cats[cat] += covered(union(ivs, lo, hi))
        a2a = union(by_cat.get("alltoall", ()), lo, hi)
        rest = union([iv for c, ivs in by_cat.items() if c != "alltoall"
                      for iv in ivs], lo, hi)
        exposed += covered(subtract(a2a, rest))
        if dev == devices[0]:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append([_covering_span(spans, s, e), (e - s) / 1e9])
    span_s = collections.defaultdict(list)
    for name, s, e in spans:
        span_s[name].append((e - s) / 1e9)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return Summary(
        window_s=window / 1e9, queries=queries, devices=n,
        busy_s=busy / n / 1e9,
        category_s={c: v / n / 1e9 for c, v in sorted(cats.items())},
        alltoall_exposed_s=exposed / n / 1e9, span_s=dict(span_s),
        device_ops=[[k, v / n / 1e9] for k, v in top_ops],
        idle_gaps=sorted(gaps, key=lambda g: -g[1])[:10])
