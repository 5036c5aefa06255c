"""Host milliseconds per query in the engine's ``engine.dispatch`` span:
the jitted call of a plan-cache hit (enqueue)."""


def read(summary):
    spans = summary.engine_span_s.get("engine.dispatch")
    if not spans or not summary.queries:
        return None
    return 1e3 * sum(spans) / summary.queries
