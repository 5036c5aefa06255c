"""Device milliseconds per query under the engine's ``engine.join`` scope:
the local join (sorts, searches, output gathers; its exchanges not
included). Mean over devices."""


def read(summary):
    s = summary.operator_s.get("engine.join", 0.0)
    if not s or not summary.queries:
        return None
    return 1e3 * s / summary.queries
