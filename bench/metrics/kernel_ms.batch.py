"""Device milliseconds per query in Pallas kernels (``tpu_custom_call``),
mean over devices."""


def read(summary):
    s = summary.category_s.get("kernel", 0.0)
    if not s or not summary.queries:
        return None
    return 1e3 * s / summary.queries
