"""TPC-H Q12, the shipping modes and order priority query (clause 2.4.12).

    select l_shipmode,
           sum(case when o_orderpriority in ('1-URGENT', '2-HIGH')
               then 1 else 0 end) as high_line_count,
           sum(case when o_orderpriority not in ('1-URGENT', '2-HIGH')
               then 1 else 0 end) as low_line_count
    from orders, lineitem
    where o_orderkey = l_orderkey
      and l_shipmode in ('[SHIPMODE1]', '[SHIPMODE2]')
      and l_commitdate < l_receiptdate and l_shipdate < l_commitdate
      and l_receiptdate >= date '[DATE]'
      and l_receiptdate < date '[DATE]' + interval '1' year
    group by l_shipmode
    order by l_shipmode

The two CASE expressions are the materialised ``o_high`` / ``o_low``
columns of ORDERS. Every number compared is an integer count: the
comparison is exact.
"""
from __future__ import annotations

import datetime

import numpy as np

from bench.gen.tpch import SHIPMODE, day

READS = {"lineitem": ("orderkey", "shipmode", "shipdate", "commitdate",
                      "receiptdate"),
         "orders": ("orderkey", "o_high")}
EXACT = ("shipmode", "o_high_sum", "o_low_sum")


def _window(params: dict):
    first = datetime.date.fromisoformat(params["date"])
    modes = tuple(SHIPMODE.index(m) for m in params["shipmodes"])
    return modes, day(params["date"]), day(first.replace(
        year=first.year + 1).isoformat())


def _keep(li: dict, params: dict, xp=np):
    (m1, m2), lo, hi = _window(params)
    return (((li["shipmode"] == m1) | (li["shipmode"] == m2))
            & (li["commitdate"] < li["receiptdate"])
            & (li["shipdate"] < li["commitdate"])
            & (li["receiptdate"] >= lo) & (li["receiptdate"] < hi))


def build(session, params: dict):
    """The query as a user writes it against the session's catalog."""
    window = _window(params)
    return (session.frame("lineitem")
            .select(lambda c: _keep(c, params), key=("q12", window))
            .join(session.frame("orders"), "orderkey")
            .groupby("shipmode", [("o_high", "sum"), ("o_low", "sum")]))


def reference(tables: dict, params: dict, drop_tail: float = 0.0) -> dict:
    """Plain NumPy Q12: filter, then a sort-merge join of the kept
    lineitems with ORDERS by orderkey, then counts per shipmode.
    ``drop_tail`` leaves that share of every lineitem chunk out (the
    control that breaks the no-row-dropped guarantee)."""
    li, od = tables["lineitem"], tables["orders"]
    keep = _keep(li, params)
    if drop_tail:
        chunks = tables["chips"]
        rows = len(keep) // chunks
        cut = rows - int(rows * drop_tail)
        keep = keep & (np.arange(len(keep)) % rows < cut)
    lkey, mode = li["orderkey"][keep], li["shipmode"][keep]
    okey, ohigh = od["orderkey"], od["o_high"]
    if not np.all(okey[1:] > okey[:-1]):
        order = np.argsort(okey, kind="stable")
        okey, ohigh = okey[order], ohigh[order]
    at = np.clip(np.searchsorted(okey, lkey), 0, len(okey) - 1)
    hit = okey[at] == lkey
    high = ohigh[at][hit].astype(np.int64)
    mode = mode[hit]
    modes = np.unique(mode)
    return {"shipmode": modes.astype(np.int32),
            "o_high_sum": np.array([high[mode == m].sum() for m in modes]),
            "o_low_sum": np.array([(1 - high[mode == m]).sum()
                                   for m in modes])}


def control(tables: dict, params: dict) -> dict:
    """The reference with the no-row-dropped guarantee broken: the last
    1% of every chip's lineitem rows left out."""
    return reference(tables, params, drop_tail=0.01)


def compare(got: dict, want: dict) -> dict:
    """Rows that differ from the reference, after ORDER BY shipmode."""
    n_got, n_want = len(got["shipmode"]), len(want["shipmode"])
    if n_got != n_want:
        return {"rows_wrong": abs(n_got - n_want) + n_want}
    order = np.argsort(got["shipmode"], kind="stable")
    wrong = np.zeros(n_want, bool)
    for c in EXACT:
        wrong |= np.asarray(got[c])[order].astype(np.int64) != want[c]
    return {"rows_wrong": int(wrong.sum())}
