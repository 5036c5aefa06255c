"""Device milliseconds per query in all-to-all ops while no other op
runs on that device (the exchange not hidden behind compute), mean over
devices. Nothing to read on one chip, where every exchange is elided."""


def read(summary):
    if not summary.category_s.get("alltoall") or not summary.queries:
        return None
    return 1e3 * summary.alltoall_exposed_s / summary.queries
