"""Device milliseconds per query under the engine's ``engine.filter``
scope in TPC-H Q1: the shipdate predicate and the compaction of the ~98%
of lineitem it keeps. Mean over devices."""


def read(summary):
    s = summary.operator_s.get("engine.filter", 0.0)
    if not s or not summary.queries:
        return None
    return 1e3 * s / summary.queries
