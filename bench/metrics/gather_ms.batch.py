"""Device milliseconds per query in gather ops (the row reorders of
compaction after a filter and of ``sort_by``), mean over devices."""


def read(summary):
    s = summary.category_s.get("gather", 0.0)
    if not s or not summary.queries:
        return None
    return 1e3 * s / summary.queries
