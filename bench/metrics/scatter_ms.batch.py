"""Device milliseconds per query in scatter ops (the XLA scatter
fallback of the segmented reductions), mean over devices."""


def read(summary):
    s = summary.category_s.get("scatter", 0.0)
    if not s or not summary.queries:
        return None
    return 1e3 * s / summary.queries
