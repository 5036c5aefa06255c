"""Device milliseconds per query under the engine's ``engine.exchange``
scope: the exchanges (destination hashes and histograms, packing,
all-to-all, unpacking), wherever they run. Mean over devices."""


def read(summary):
    s = summary.operator_s.get("engine.exchange", 0.0)
    if not s or not summary.queries:
        return None
    return 1e3 * s / summary.queries
