"""On-device TPC-H data generator: dbgen's schema and value distributions.

TPC Benchmark H Standard Specification, clause 4.2.3 (the dbgen rules for
ORDERS and LINEITEM). Every chip draws its own slice of the orders by
orderkey range, and the lineitems of the previous chip's range, from
``--seed`` in one jitted ``shard_map`` call; nothing is made on the host.

Departures from dbgen, each listed under ``assumed`` in the configuration
files:

* flags and modes are int32 dictionary codes, numbered in the strings'
  sort order (so ORDER BY on the code is ORDER BY on the string);
* dates are int32 days since 1992-01-01;
* decimal(15,2) columns are float32;
* the free-text comment columns are not held;
* the number of lines per order takes each value 1..7 for a seventh of
  each chip's orders, in a seeded random order (dbgen draws each order's
  count on its own), so every seed yields the same row counts and the
  cost-sized query plans, which read those counts, are the same programs;
* random values come from JAX's threefry generator, not dbgen's streams.

Derived columns that the engine cannot compute in a query (it has no
computed projection or CASE) are materialised here: ``disc_price``,
``charge``, and ``o_high`` / ``o_low`` (orderpriority 1-URGENT or 2-HIGH).
"""
from __future__ import annotations

import datetime

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

#: dictionary codes, in sort order of the strings they stand for
RETURNFLAG = ("A", "N", "R")
LINESTATUS = ("F", "O")
SHIPMODE = ("AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK")
ORDERPRIORITY = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED",
                 "5-LOW")
ORDERSTATUS = ("F", "O", "P")
SHIPINSTRUCT = ("COLLECT COD", "DELIVER IN PERSON", "NONE",
                "TAKE BACK RETURN")

EPOCH = datetime.date(1992, 1, 1)


def day(iso: str) -> int:
    """A date as the int32 code the tables hold: days since 1992-01-01."""
    return (datetime.date.fromisoformat(iso) - EPOCH).days


STARTDATE = day("1992-01-01")
CURRENTDATE = day("1995-06-17")
ENDDATE = day("1998-12-31")

LINEITEM_COLUMNS = (
    "orderkey", "partkey", "suppkey", "linenumber", "quantity",
    "extendedprice", "discount", "tax", "returnflag", "linestatus",
    "shipdate", "commitdate", "receiptdate", "shipinstruct", "shipmode",
    "disc_price", "charge")
ORDERS_COLUMNS = (
    "orderkey", "custkey", "orderstatus", "totalprice", "orderdate",
    "orderpriority", "clerk", "shippriority", "o_high", "o_low")


def orders_per_chip(scale_factor: float, chips: int) -> int:
    n = round(scale_factor * 1_500_000)
    if n % chips:
        raise ValueError(f"{n} orders do not split over {chips} chips")
    return n // chips


def lines_per_chip(n_orders: int) -> int:
    """Lineitem rows of ``n_orders`` orders: counts cycle 1..7."""
    full, rest = divmod(n_orders, 7)
    return 28 * full + rest * (rest + 1) // 2


def _randint(key, n, lo, hi):
    """n int32 values uniform on [lo, hi] (both ends included)."""
    return jax.random.randint(key, (n,), lo, hi + 1, dtype=jnp.int32)


def _shard(key, shard, *, sf: float, n: int):
    """One chip's orders (n rows) and their lineitems."""
    ks = jax.random.split(jax.random.fold_in(key, shard), 20)
    n_lines = lines_per_chip(n)

    # -- ORDERS (clause 4.2.3) -------------------------------------------
    idx = shard * n + jnp.arange(n, dtype=jnp.int32) + 1
    # sparse keys: only the first 8 of every 32 keys are used
    orderkey = ((idx >> 3) << 5) + (idx & 7)
    lines = jax.random.permutation(
        ks[0], (jnp.arange(n, dtype=jnp.int32) % 7) + 1)
    orderdate = _randint(ks[1], n, STARTDATE, ENDDATE - 151)
    n_cust = round(sf * 150_000)
    # custkey in [1, n_cust], never a multiple of 3: u -> u + u // 2 + 1
    u = _randint(ks[2], n, 0, n_cust - n_cust // 3 - 1)
    custkey = u + u // 2 + 1
    orderpriority = _randint(ks[3], n, 0, len(ORDERPRIORITY) - 1)
    clerk = _randint(ks[4], n, 1, max(1, round(sf * 1_000)))

    # -- LINEITEM --------------------------------------------------------
    start = jnp.cumsum(lines) - lines
    first = jnp.zeros((n_lines,), jnp.int32).at[start].set(1)
    order_of = jnp.cumsum(first) - 1
    linenumber = jnp.arange(n_lines, dtype=jnp.int32) - start[order_of] + 1
    n_part = round(sf * 200_000)
    n_supp = round(sf * 10_000)
    partkey = _randint(ks[5], n_lines, 1, n_part)
    corner = _randint(ks[6], n_lines, 0, 3)
    suppkey = (partkey + corner * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1
    quantity = _randint(ks[7], n_lines, 1, 50)
    # P_RETAILPRICE in cents: 90000 + (partkey/10 mod 20001) + 100 *
    # (partkey mod 1000); times quantity it stays below 2^24, so exact
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    extendedprice = (quantity * retail).astype(jnp.float32) / 100
    discount = _randint(ks[8], n_lines, 0, 10).astype(jnp.float32) / 100
    tax = _randint(ks[9], n_lines, 0, 8).astype(jnp.float32) / 100
    od = orderdate[order_of]
    shipdate = od + _randint(ks[10], n_lines, 1, 121)
    commitdate = od + _randint(ks[11], n_lines, 30, 90)
    receiptdate = shipdate + _randint(ks[12], n_lines, 1, 30)
    r_or_a = jnp.where(_randint(ks[13], n_lines, 0, 1) == 0,
                       RETURNFLAG.index("R"), RETURNFLAG.index("A"))
    returnflag = jnp.where(receiptdate <= CURRENTDATE, r_or_a,
                           RETURNFLAG.index("N")).astype(jnp.int32)
    linestatus = (shipdate > CURRENTDATE).astype(jnp.int32)  # F=0, O=1
    shipinstruct = _randint(ks[14], n_lines, 0, len(SHIPINSTRUCT) - 1)
    shipmode = _randint(ks[15], n_lines, 0, len(SHIPMODE) - 1)
    disc_price = extendedprice * (1 - discount)
    charge = disc_price * (1 + tax)

    # ORDERS columns that sum over their lines
    seg = dict(segment_ids=order_of, num_segments=n, indices_are_sorted=True)
    totalprice = jax.ops.segment_sum(charge, **seg)
    n_open = jax.ops.segment_sum(linestatus, **seg)
    orderstatus = jnp.where(n_open == 0, ORDERSTATUS.index("F"),
                            jnp.where(n_open == lines, ORDERSTATUS.index("O"),
                                      ORDERSTATUS.index("P")))
    o_high = (orderpriority <= ORDERPRIORITY.index("2-HIGH")).astype(
        jnp.int32)
    lineitem = dict(
        orderkey=orderkey[order_of], partkey=partkey, suppkey=suppkey,
        linenumber=linenumber, quantity=quantity.astype(jnp.float32),
        extendedprice=extendedprice, discount=discount, tax=tax,
        returnflag=returnflag, linestatus=linestatus, shipdate=shipdate,
        commitdate=commitdate, receiptdate=receiptdate,
        shipinstruct=shipinstruct, shipmode=shipmode,
        disc_price=disc_price, charge=charge)
    orders = dict(
        orderkey=orderkey, custkey=custkey,
        orderstatus=orderstatus.astype(jnp.int32), totalprice=totalprice,
        orderdate=orderdate, orderpriority=orderpriority, clerk=clerk,
        shippriority=jnp.zeros((n,), jnp.int32), o_high=o_high,
        o_low=1 - o_high)
    return {"lineitem": lineitem, "orders": orders}


def seed_key(seed: int):
    """A PRNG key from every bit of a non-negative seed of up to 64 bits
    (``jax.random.key`` alone keeps only the low 32 without x64)."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is not in [0, 2^64)")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def program(config: dict, mesh, axis_name: str):
    """The jitted generator of the configuration's tables on ``mesh``
    (a function of the PRNG key), and the rows per chip of each table.

    Chip ``c`` holds the orders of index range ``c`` and the lineitems of
    range ``c - 1`` (mod the chips): the two tables are placed by
    different keys, as a loader that gives each table's chunks to chips on
    its own would place them, so a join on several chips must exchange.
    """
    chips = mesh.shape[axis_name]
    sf = config["scale_factor"]
    n = orders_per_chip(sf, chips)
    held = {t: tuple(spec["columns"]) for t, spec in config["tables"].items()}

    def body(key):
        shard = jax.lax.axis_index(axis_name)
        out = _shard(key, shard, sf=sf, n=n)
        if chips > 1:
            out["lineitem"] = _shard(key, (shard - 1) % chips, sf=sf,
                                     n=n)["lineitem"]
        return {t: {c: out[t][c] for c in cols} for t, cols in held.items()}

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(),
                               out_specs=P(axis_name)))
    return fn, {"orders": n, "lineitem": lines_per_chip(n)}


def generate(config: dict, seed: int, mesh, axis_name: str):
    """The configuration's tables on ``mesh``, placed along ``axis_name``
    as :func:`program` says.

    Returns ``{table: (columns, rows_per_chip)}``: each column a global
    array of ``chips * rows_per_chip`` rows sharded on its leading axis,
    holding only the columns the configuration lists.
    """
    fn, rows = program(config, mesh, axis_name)
    key = jax.device_put(seed_key(seed), NamedSharding(mesh, P()))
    tables = fn(key)
    return {t: (cols, rows[t]) for t, cols in tables.items()}
