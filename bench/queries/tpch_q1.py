"""TPC-H Q1, the pricing summary report (specification clause 2.4.1).

    select l_returnflag, l_linestatus, sum(l_quantity), sum(l_extendedprice),
           sum(l_extendedprice * (1 - l_discount)),
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
           avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
    from lineitem
    where l_shipdate <= date '1998-12-01' - interval '[DELTA]' day
    group by l_returnflag, l_linestatus
    order by l_returnflag, l_linestatus

The two products are the materialised ``disc_price`` and ``charge``
columns. The ORDER BY over four rows is done by :func:`compare`, which
sorts both sides by the group keys.
"""
from __future__ import annotations

import numpy as np

from bench.gen.tpch import day

KEYS = ("returnflag", "linestatus")
AGGS = (("quantity", "sum"), ("extendedprice", "sum"), ("disc_price", "sum"),
        ("charge", "sum"), ("quantity", "mean"), ("extendedprice", "mean"),
        ("discount", "mean"), ("quantity", "count"))
FLOATS = ("quantity", "extendedprice", "disc_price", "charge", "discount")

#: the input columns the reference reads
READS = {"lineitem": KEYS + FLOATS + ("shipdate",)}
#: the result's exact columns, and those compared by relative error
EXACT = KEYS + ("quantity_count",)
RELATIVE = tuple(f"{c}_{op}" for c, op in AGGS if op != "count")


def cutoff(params: dict) -> int:
    return day("1998-12-01") - int(params["delta_days"])


def build(session, params: dict):
    """The query as a user writes it against the session's catalog."""
    last = cutoff(params)
    return (session.frame("lineitem")
            .select(lambda c: c["shipdate"] <= last, key=("q1.shipdate<=", last))
            .groupby(list(KEYS), list(AGGS)))


def reference(tables: dict, params: dict) -> dict:
    """Plain NumPy Q1 over the host copy of the input columns, summed in
    float64."""
    li = tables["lineitem"]
    keep = li["shipdate"] <= cutoff(params)
    rf, ls = li["returnflag"][keep], li["linestatus"][keep]
    group = rf.astype(np.int64) * 2 + ls
    n = int(group.max(initial=-1)) + 1
    count = np.bincount(group, minlength=n)
    have = np.flatnonzero(count)
    out = {"returnflag": (have // 2).astype(np.int32),
           "linestatus": (have % 2).astype(np.int32),
           "quantity_count": count[have].astype(np.int64)}
    for col in FLOATS:
        vals = li[col][keep].astype(np.float64)
        s = np.bincount(group, weights=vals, minlength=n)[have]
        out[f"{col}_sum"] = s
        out[f"{col}_mean"] = s / count[have]
    return {k: out[k] for k in EXACT + RELATIVE}


def control(tables: dict, params: dict) -> dict:
    """The reference computed one precision below the configuration's
    float32: every money column rounded to bfloat16 and each group's sums
    accumulated in bfloat16, one row after another (the order the
    engine's scatter adds in)."""
    import ml_dtypes

    bf16 = ml_dtypes.bfloat16
    li = tables["lineitem"]
    keep = li["shipdate"] <= cutoff(params)
    group = li["returnflag"][keep].astype(np.int64) * 2 \
        + li["linestatus"][keep]
    want = reference(tables, params)
    have = want["returnflag"].astype(np.int64) * 2 + want["linestatus"]
    out = {k: want[k] for k in EXACT}
    for col in FLOATS:
        vals = li[col][keep].astype(bf16)
        s = np.array([np.cumsum(vals[group == g], dtype=bf16)[-1]
                      for g in have]).astype(np.float64)
        out[f"{col}_sum"] = s
        out[f"{col}_mean"] = s / want["quantity_count"]
    return {k: out[k] for k in EXACT + RELATIVE}


def column_errors(got: dict, want: dict) -> dict:
    """Relative error of each float aggregate (for the calibration log)."""
    order = np.lexsort([got[k] for k in reversed(KEYS)])
    return {c: float(np.max(np.abs(np.asarray(got[c])[order]
                                   .astype(np.float64) - want[c])
                            / np.abs(want[c]))) for c in RELATIVE}


def compare(got: dict, want: dict) -> dict:
    """The numbers one result is judged by: key and count mismatches
    (exact) and the widest relative error of a float aggregate."""
    if len(got[KEYS[0]]) != len(want[KEYS[0]]):
        return {"groups_wrong": abs(len(got[KEYS[0]]) - len(want[KEYS[0]]))
                + len(want[KEYS[0]]), "count_diff": float("inf"),
                "money_rel_err": float("inf")}
    order = np.lexsort([got[k] for k in reversed(KEYS)])
    got = {k: np.asarray(v)[order] for k, v in got.items()}
    wrong = np.zeros(len(order), bool)
    for k in KEYS:
        wrong |= got[k] != want[k]
    count_diff = np.abs(got["quantity_count"].astype(np.int64)
                        - want["quantity_count"])
    rel = max(float(np.max(np.abs(got[c].astype(np.float64) - want[c])
                           / np.abs(want[c]))) for c in RELATIVE)
    return {"groups_wrong": int(wrong.sum()),
            "count_diff": float(count_diff.max(initial=0)),
            "money_rel_err": rel}
