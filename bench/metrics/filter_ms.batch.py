"""Device milliseconds per query under the engine's ``engine.filter``
scope: the filter (predicate and compaction of the rows it keeps). Mean
over devices."""


def read(summary):
    s = summary.operator_s.get("engine.filter", 0.0)
    if not s or not summary.queries:
        return None
    return 1e3 * s / summary.queries
