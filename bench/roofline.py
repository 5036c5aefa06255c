"""Device time and bytes of each jitted function a program calls, and a
kernel's share of the chip's memory roofline.

Every kernel entry of ``repro.kernels.ops`` (``hash32``,
``bucket_histogram``, ``_segment_reduce_jit``, ``_segment_scan_jit``,
``sort_pairs``) is a jitted function, so its name reaches two places:

- the traced jaxpr of the program, as a ``jit`` equation with that
  ``name``: :func:`call_bytes` counts the logical operands and results of
  each call once, per device;
- each instruction's ``op_name`` in the compiled program, as
  ``jit(<name>)``: :func:`hlo_calls` maps instructions to the names they
  run under, and :func:`call_seconds` reduces a trace's window to device
  seconds per name.

The bytes are what any implementation of the call has to read and write
at the least, whichever route runs it (the Pallas kernel or its XLA
fallback), so a share cannot read high because the code moved fewer
bytes. A call whose results nothing uses is dead code, which the
compiler removes: the jaxpr is pruned of it first, at every depth, and it
counts nothing. A call inside a ``scan`` counts its trip count; one inside
a ``while`` or a ``cond``, whose trip count the jaxpr does not fix, leaves
its name without a byte count, and the share then gives no reading.
"""
from __future__ import annotations

import collections
import math
import re

from bench import tracing

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_JIT = re.compile(r"jit\(([^()/]+)\)")


def _bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    itemsize = getattr(getattr(aval, "dtype", None), "itemsize", None)
    if shape is None or not isinstance(itemsize, int):
        return 0
    return math.prod(shape) * itemsize


def _eqn_bytes(eqn) -> int:
    """Logical bytes of one live call: each distinct operand once, and
    each result."""
    seen, total = set(), 0
    for v in eqn.invars:
        if hasattr(v, "val") or id(v) not in seen:  # a literal, or a new var
            seen.add(id(v))
            total += _bytes(v.aval)
    return total + sum(_bytes(v.aval) for v in eqn.outvars)


def _jaxprs(params: dict):
    """The jaxprs an equation's parameters hold, closed or open."""
    for v in params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if hasattr(x, "eqns"):
                yield x
            elif hasattr(getattr(x, "jaxpr", None), "eqns"):
                yield x.jaxpr


def call_bytes(jaxpr, devices: int = 1) -> dict[str, int | None]:
    """Name -> logical bytes per device of every call of that jitted
    function in one run of ``jaxpr`` (a traced program's jaxpr, closed or
    open), walking into ``shard_map``, nested jits and loops once the
    calls and results that no output depends on are pruned; None where
    the bytes are not fixed: a call under a ``while`` or a ``cond``, or a
    call outside ``shard_map`` in a program over several devices. A
    ``pallas_call``'s own body is a kernel's block program, not walked."""
    from jax.interpreters import partial_eval as pe

    total: dict[str, int] = collections.Counter()
    unknown: set[str] = set()

    def walk(jx, times, local):
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim == "jit":
                name = eqn.params["name"]
                if times is None or not (local or devices == 1):
                    unknown.add(name)
                else:
                    total[name] += times * _eqn_bytes(eqn)
            if prim == "pallas_call":
                continue
            inner = times
            if prim == "scan" and times is not None:
                inner = times * int(eqn.params["length"])
            elif prim in ("while", "cond"):
                inner = None
            for sub in _jaxprs(eqn.params):
                walk(sub, inner, local or prim == "shard_map")

    jx = getattr(jaxpr, "jaxpr", jaxpr)
    live, _ = pe.dce_jaxpr(jx, [True] * len(jx.outvars), instantiate=True)
    walk(live, 1, False)
    return {n: None if n in unknown else total[n]
            for n in sorted(set(total) | unknown)}


def add_bytes(into: dict, counted: dict, runs: int = 1) -> dict:
    """``into`` plus ``runs`` times ``counted`` (both from
    :func:`call_bytes`); a name without a count in either has none."""
    out = dict(into)
    for n, b in counted.items():
        have = out.get(n, 0)
        out[n] = None if b is None or have is None else have + runs * b
    return out


def hlo_calls(hlo_text: str) -> dict[str, list[str]]:
    """Instruction name -> the jitted functions it runs under, innermost
    last, from each ``op_name`` of one compiled module's text (the
    program's own outermost ``jit`` left out). Instructions under no
    nested function are left out."""
    out = {}
    for line in hlo_text.splitlines():
        m = tracing._INSTR.match(line)
        if not m:
            continue
        meta = _OP_NAME.search(m.group(2))
        if meta:
            names = _JIT.findall(meta.group(1))
            if meta.group(1).startswith("jit("):
                names = names[1:]
            if names:
                out[m.group(1)] = names
    return out


def call_seconds(records: dict, calls: dict[str, dict[str, list]]
                 ) -> dict[str, float]:
    """Device seconds per jitted function over the window of a
    :func:`bench.tracing.load` record: per device the union of the
    intervals of the instructions under that name, then the mean over
    devices. ``calls`` maps a module name to :func:`hlo_calls` of its
    compiled text."""
    anywhere = {k: v for c in calls.values() for k, v in c.items()}
    lo, hi = tracing._window(records)
    devices = sorted(records["ops"])
    out: dict[str, float] = collections.Counter()
    for dev in devices:
        modules = records.get("modules", {}).get(dev, [])
        by_name = collections.defaultdict(list)
        for text, s, e in records["ops"][dev]:
            known = calls.get(tracing._module_of(modules, s), {}) \
                if modules else anywhere
            for n in set(known.get(tracing.op_name(text), ())):
                by_name[n].append((s, e))
        for n, ivs in by_name.items():
            out[n] += tracing.covered(tracing.union(ivs, lo, hi))
    return {n: v / len(devices) / 1e9 for n, v in sorted(out.items())}


def share(summary, name: str) -> float | None:
    """Percent of the HBM roofline that the calls of jitted function
    ``name`` reached in the window: their logical bytes over their device
    time times the chip's HBM bytes per second. None where the calls ran
    no instruction, their bytes are not fixed, or no peak is known."""
    b = summary.call_bytes.get(name)
    s = summary.call_s.get(name)
    bw = summary.hbm_bytes_per_s
    if not b or not s or not bw:
        return None
    return 100.0 * b / (s * bw)
