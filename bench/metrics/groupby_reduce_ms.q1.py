"""Device milliseconds per query in the groupby's segment reductions in
TPC-H Q1: the ``engine.step.reduce`` step inside ``engine.groupby`` (the
sums, means and count, on whichever route ``kops.segment_reduce``
takes). Nothing to read where the program has no such step. Mean over
devices."""


def read(summary):
    s = summary.step_s.get("engine.groupby/engine.step.reduce", 0.0)
    if not s or not summary.queries:
        return None
    return 1e3 * s / summary.queries
