"""Read the engine's host spans back from a profiler trace (tests)."""
from __future__ import annotations

import glob
import os
import warnings


def engine_spans(trace_dir: str) -> list:
    """``[name, start_ns, end_ns, stats]`` of every ``engine.*`` host span
    in the one trace under ``trace_dir``, sorted by start."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith("engine."):
                    continue
                with warnings.catch_warnings():
                    # jaxlib's event stats warn on iteration (Python 3.12)
                    warnings.simplefilter("ignore", DeprecationWarning)
                    stats = dict(ev.stats)
                start = int(ev.start_ns)
                out.append([ev.name, start, start + int(ev.duration_ns),
                            stats])
    return sorted(out, key=lambda r: r[1])
