"""Device milliseconds per query in XLA sort ops (the local operators'
sorts; large sorts fall to ``lax.sort``), mean over devices."""


def read(summary):
    s = summary.category_s.get("sort", 0.0)
    if not s or not summary.queries:
        return None
    return 1e3 * s / summary.queries
