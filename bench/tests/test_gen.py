"""The on-device TPC-H generator against dbgen's rules (clause 4.2.3)."""
import json

import jax
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh

from bench.gen import tpch
from bench.run import BENCH

SF = 0.01


def _mesh(chips):
    return Mesh(np.asarray(jax.devices()[:chips]), ("shuffle",),
                axis_types=(AxisType.Auto,))


def _tables(chips, seed):
    config = json.loads((BENCH / "configs" / "tpch_sf40_4chip.json")
                        .read_text())
    config["scale_factor"] = SF
    out = tpch.generate(config, seed, _mesh(chips), "shuffle")
    return {t: {c: np.asarray(v) for c, v in cols.items()}
            for t, (cols, _) in out.items()}, \
        {t: rows for t, (_, rows) in out.items()}


@pytest.mark.parametrize("chips", [1, 4])
def test_columns_follow_dbgen(chips):
    t, rows = _tables(chips, 2 ** 31 + 17)
    li, od = t["lineitem"], t["orders"]
    n = round(SF * 1_500_000)
    assert rows["orders"] * chips == n == len(od["orderkey"])
    assert rows["lineitem"] == tpch.lines_per_chip(n // chips)
    # sparse, unique, sorted order keys: 8 of every 32
    assert np.all(np.diff(od["orderkey"]) > 0)
    assert set(np.unique(od["orderkey"] % 32)) <= set(range(1, 8)) | {0}
    assert np.all((od["orderkey"] & 31) < 8)
    assert np.isin(li["orderkey"], od["orderkey"]).all()
    lines = np.bincount(np.searchsorted(od["orderkey"], li["orderkey"]))
    assert lines.min() == 1 and lines.max() == 7
    assert np.all(od["custkey"] % 3 != 0)
    assert 1 <= od["custkey"].min() and od["custkey"].max() <= SF * 150_000
    assert li["quantity"].min() == 1 and li["quantity"].max() == 50
    assert np.all(np.isclose(li["discount"] * 100,
                             np.round(li["discount"] * 100)))
    assert 0 <= li["discount"].min() and li["discount"].max() <= 0.1 + 1e-6
    assert 0 <= li["tax"].min() and li["tax"].max() <= 0.08 + 1e-6
    od_of = od["orderdate"][np.searchsorted(od["orderkey"], li["orderkey"])]
    assert od["orderdate"].min() >= tpch.STARTDATE
    assert od["orderdate"].max() <= tpch.ENDDATE - 151
    assert np.all((li["shipdate"] - od_of >= 1) & (li["shipdate"] - od_of
                                                   <= 121))
    assert np.all((li["commitdate"] - od_of >= 30)
                  & (li["commitdate"] - od_of <= 90))
    d = li["receiptdate"] - li["shipdate"]
    assert d.min() >= 1 and d.max() <= 30
    late = li["receiptdate"] > tpch.CURRENTDATE
    assert np.all(li["returnflag"][late] == tpch.RETURNFLAG.index("N"))
    assert set(np.unique(li["returnflag"][~late])) == {0, 2}
    assert np.all(li["linestatus"] == (li["shipdate"] > tpch.CURRENTDATE))
    np.testing.assert_allclose(li["disc_price"],
                               li["extendedprice"] * (1 - li["discount"]),
                               rtol=1e-6)
    assert set(np.unique(li["shipmode"])) == set(range(7))
    assert np.all(od["o_high"] == (od["orderpriority"] <= 1))
    assert np.all(od["o_low"] == 1 - od["o_high"])


def test_lineitem_lies_one_chip_after_its_orders():
    t, rows = _tables(4, 2 ** 31 + 19)
    li = t["lineitem"]["orderkey"].reshape(4, rows["lineitem"])
    od = t["orders"]["orderkey"].reshape(4, rows["orders"])
    for c in range(4):
        assert np.isin(li[c], od[(c - 1) % 4]).all()
        assert not np.isin(li[c], od[c]).any()


def test_seed_changes_values_not_sizes():
    a, ra = _tables(1, 5)
    b, rb = _tables(1, 5 + (1 << 32))  # differs only above 32 bits
    c, _ = _tables(1, 5)
    assert ra == rb
    assert not np.array_equal(a["lineitem"]["partkey"],
                              b["lineitem"]["partkey"])
    assert np.array_equal(a["lineitem"]["partkey"], c["lineitem"]["partkey"])


def test_query_groups():
    from bench.queries import tpch_q1, tpch_q12

    t, _ = _tables(1, 99)
    t["chips"] = 1
    q1 = tpch_q1.reference(t, {"delta_days": 90})
    assert list(zip(q1["returnflag"], q1["linestatus"])) == [
        (0, 0), (1, 0), (1, 1), (2, 0)]  # (A,F) (N,F) (N,O) (R,F)
    q12 = tpch_q12.reference(t, {"shipmodes": ["MAIL", "SHIP"],
                                 "date": "1994-01-01"})
    assert q12["shipmode"].tolist() == [tpch.SHIPMODE.index("MAIL"),
                                        tpch.SHIPMODE.index("SHIP")]
    assert (q12["o_high_sum"] > 0).all() and (q12["o_low_sum"] > 0).all()
