"""Device milliseconds per query under the engine's ``engine.groupby``
scope in TPC-H Q1: the groupby's sort, reorder and segment reductions
(one chip, so no exchange). Mean over devices."""


def read(summary):
    s = summary.operator_s.get("engine.groupby", 0.0)
    if not s or not summary.queries:
        return None
    return 1e3 * s / summary.queries
