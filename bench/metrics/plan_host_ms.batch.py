"""Host milliseconds per query in the engine's ``engine.plan`` span:
planning a query: optimize or cost model, output partitioning,
plan-cache key."""


def read(summary):
    spans = summary.engine_span_s.get("engine.plan")
    if not spans or not summary.queries:
        return None
    return 1e3 * sum(spans) / summary.queries
