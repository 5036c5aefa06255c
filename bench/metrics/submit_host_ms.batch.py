"""Host milliseconds per query inside ``collect_async()``: plan,
optimize, cost, plan-cache lookup and dispatch, from the harness's
``bench.submit`` span around the call."""


def read(summary):
    spans = summary.span_s.get("bench.submit")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
