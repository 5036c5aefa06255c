"""Cardinality statistics + sizing math for cost-model-driven planning.

Cylon's performance edge comes from choosing the right distributed
algorithm per operator and keeping buffers tight (paper §III); the
follow-up aggregation paper (arXiv:2010.14596) shows the shuffle-vs-
two-phase choice flips with key cardinality. This module supplies the
*numbers* that drive those choices in ``repro.core.plan``:

* :class:`TableStats` — row count plus per-key-column min/max and an NDV
  (number-of-distinct-values) estimate, computed by one cheap vectorized
  pass (:func:`sketch_columns`): hash each key column (the murmur3 kernel
  already on the shuffle path), scatter into a fixed bitmap, and apply
  linear counting ``ndv = -m * ln(1 - occupied/m)``. Cached on
  ``DistTable`` (``ctx.analyze``) and propagated through plan nodes by
  the per-operator estimators in ``plan.py``.

* Sizing math — AllToAll send buckets are static per-(source, dest) slot
  budgets; the cost model sizes them from *estimated occupancy* instead
  of a fixed multiple of table capacity. :func:`with_skew_margin` models
  hash placement as Poisson: budget = mean + 4*sqrt(mean) + 4, i.e. the
  mean plus ~4 standard deviations plus a small-count floor. Estimates
  can still be wrong (selectivity defaults, skewed multiplicity), so
  every stats-sized capacity is *overflow-safe*: the shuffle's overflow
  counter (and the join truncation counter it feeds) triggers a single
  recompile-with-conservative-capacity retry in ``DistContext._run_plan``
  rather than wrong results.

* ``FALLBACK_SLACK`` — THE no-stats constant. Without stats every bucket
  falls back to ``capacity * FALLBACK_SLACK / num_shards`` (the
  pre-cost-model behavior, byte-compatible). The sort path multiplies it
  by :data:`SORT_SLACK_FACTOR` because sampled range splitters miss true
  quantiles; the join output budget doubles it for the same reason the
  eager chain did (two shuffled operands land in one output). All three
  derive from the one constant below instead of scattered literals.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# --------------------------------------------------------------------------
# slack constants (the no-stats fallback path)
# --------------------------------------------------------------------------

#: The single fallback slack for every capacity derived WITHOUT statistics:
#: bucket = ceil(capacity * FALLBACK_SLACK / num_shards). Documented here,
#: referenced everywhere (plan executor, repartition defaults).
FALLBACK_SLACK = 2.0

#: Sort range-partitions by sampled splitters; quantile error concentrates
#: rows beyond hash-uniformity, so the no-stats sort bucket uses
#: FALLBACK_SLACK * SORT_SLACK_FACTOR (== the pre-cost-model 4.0).
SORT_SLACK_FACTOR = 2.0

#: No-stats join output budget: 2 * p * bucket — both shuffled operands
#: land in one output table (the historical 2x on top of FALLBACK_SLACK).
JOIN_OUT_FACTOR = 2.0

#: Selectivity assumed for a Select whose predicate we cannot evaluate
#: statically (all of them, today): the classic System R default.
DEFAULT_SELECTIVITY = 0.5

#: Multiplier on estimated mean occupancy for stats-sized SORT buckets
#: (sampled-splitter error) and range-aligned join sends.
RANGE_SIZING_FACTOR = 2.0

#: Multiplier on the estimated per-shard join match count (key
#: multiplicity concentrates matches beyond the Poisson model).
JOIN_OUT_SIZING_FACTOR = 1.5

#: Linear-counting bitmap width for the NDV sketch. Error ~ sqrt(m) *
#: exp(ndv/m) / ndv: under 3% up to ndv ~ m, degrading gracefully above.
SKETCH_BUCKETS = 4096

#: A shuffle below this wire-byte estimate runs as one collective (S=1):
#: per-collective launch overhead would swamp any comm/compute overlap.
STAGE_WIRE_THRESHOLD = 1 << 20

#: Staging ceiling — chunks beyond this buy no extra overlap (there are
#: only ~2 neighbours to hide a chunk's wire time behind) and each one is
#: another collective launch.
MAX_SHUFFLE_STAGES = 4


def pick_stages(wire_bytes: float, bucket_capacity: int) -> int:
    """Pipeline depth for a shuffle moving ``wire_bytes`` over the wire.

    S=1 below :data:`STAGE_WIRE_THRESHOLD` (small shuffles pay zero extra
    collectives), then doubles with the wire volume up to
    :data:`MAX_SHUFFLE_STAGES`, clamped so each chunk keeps at least one
    capacity slot. Every S is bit-identical; this only trades collective
    launches against comm/compute overlap.
    """
    if bucket_capacity <= 1 or wire_bytes <= STAGE_WIRE_THRESHOLD:
        return 1
    s = 2
    while s < MAX_SHUFFLE_STAGES and wire_bytes >= (2 * s) * STAGE_WIRE_THRESHOLD:
        s *= 2
    return min(s, bucket_capacity)


# --------------------------------------------------------------------------
# statistics containers
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ColumnStats:
    """Per-column statistics: NDV estimate + value range (as floats)."""

    ndv: float
    lo: float | None = None
    hi: float | None = None


@dataclasses.dataclass(frozen=True)
class TableStats:
    """Table-level statistics (hashable; static planner metadata).

    ``rows`` is exact on analyzed tables and an estimate after operator
    propagation. ``columns`` holds ColumnStats for the 1-D key-typed
    columns only (payload columns don't drive planning decisions).
    ``max_shard_rows`` is the exact per-shard max on analyzed tables
    (None once an operator has redistributed rows).
    """

    rows: float
    columns: tuple[tuple[str, ColumnStats], ...] = ()
    max_shard_rows: float | None = None

    def col(self, name: str) -> ColumnStats | None:
        for k, cs in self.columns:
            if k == name:
                return cs
        return None

    def ndv(self, keys: Sequence[str]) -> float | None:
        """Joint NDV of a key tuple: product of per-column NDVs capped by
        the row count (the standard independence upper bound). None when
        any key column has no statistics."""
        out = 1.0
        cap = max(self.rows, 1.0)
        for k in keys:
            cs = self.col(k)
            if cs is None:
                return None
            out *= max(cs.ndv, 1.0)
            if out >= cap:
                return cap
        return min(out, cap)

    def shard_rows(self, p: int) -> float:
        """Per-source-shard row estimate (exact max when known)."""
        if self.max_shard_rows is not None:
            return self.max_shard_rows
        return self.rows / max(p, 1)


def cap_rows(stats: TableStats, rows: float,
             keep: Sequence[str] | None = None) -> TableStats:
    """Derive propagated stats: new row count, per-column NDVs capped at
    it (a table of r rows has at most r distinct values per column), and
    optionally only the ``keep`` columns surviving."""
    rows = max(rows, 0.0)
    cols = []
    for k, cs in stats.columns:
        if keep is not None and k not in keep:
            continue
        cols.append((k, ColumnStats(min(cs.ndv, max(rows, 1.0)),
                                    cs.lo, cs.hi)))
    return TableStats(rows=rows, columns=tuple(cols), max_shard_rows=None)


# --------------------------------------------------------------------------
# bucket sizing (the Poisson skew model)
# --------------------------------------------------------------------------


def with_skew_margin(mean: float) -> int:
    """Slot budget for an expected occupancy of ``mean`` rows: the mean
    plus ~4 Poisson standard deviations plus a small-count floor. Tighter
    than a fixed multiple at scale, safe at small counts — and every
    consumer is backed by the overflow-retry path regardless.

    The ``stats.estimate`` fault site lives here: an armed fault derates
    the budget (divides by ``FaultPlan.factor``), modeling a badly wrong
    cardinality estimate — the chaos probe for the overflow-retry rung.
    """
    mean = max(mean, 0.0)
    budget = max(1, math.ceil(mean + 4.0 * math.sqrt(mean) + 4.0))
    from repro.core import faults as FLT

    fp = FLT.check("stats.estimate")
    if fp is not None:
        budget = max(1, int(budget // max(fp.factor, 1.0)))
    return budget


def size_bucket(source_rows: float, p: int, factor: float = 1.0) -> int:
    """Per-(source, dest) send-slot budget given ``source_rows`` rows per
    source shard hashed over ``p`` destinations. ``factor`` scales the
    mean for skew-prone placements (range partition: sampling error)."""
    return with_skew_margin(factor * max(source_rows, 0.0) / max(p, 1))


def size_output(rows: float, p: int, factor: float = 1.0) -> int:
    """Per-shard output budget for ``rows`` estimated global result rows
    hash-spread over ``p`` shards."""
    return with_skew_margin(factor * max(rows, 0.0) / max(p, 1))


# --------------------------------------------------------------------------
# the analysis pass (one vectorized sweep per table)
# --------------------------------------------------------------------------


def _sketch_one(col: jax.Array, valid: jax.Array, axis_name=None):
    """(occupied-bitmap-count, min, max) of a 1-D key column as f32/i32
    scalars — traced; the host wrapper turns them into ColumnStats.
    Inside a shard_map over ``axis_name`` each shard sketches its own rows
    and the bitmaps and bounds merge across shards."""
    from repro.kernels import ops as kops

    h = kops.hash32(col, seed=5)
    b = jnp.where(valid, (h % jnp.uint32(SKETCH_BUCKETS)).astype(jnp.int32),
                  SKETCH_BUCKETS)
    occ = jnp.zeros((SKETCH_BUCKETS,), jnp.int32).at[b].set(1, mode="drop")
    if jnp.issubdtype(col.dtype, jnp.floating):
        lo_s, hi_s = jnp.inf, -jnp.inf
    else:
        info = jnp.iinfo(col.dtype)
        lo_s, hi_s = info.max, info.min
    lo = jnp.min(jnp.where(valid, col, jnp.asarray(lo_s, col.dtype)))
    hi = jnp.max(jnp.where(valid, col, jnp.asarray(hi_s, col.dtype)))
    if axis_name is not None:
        occ = jax.lax.pmax(occ, axis_name)
        lo = jax.lax.pmin(lo, axis_name)
        hi = jax.lax.pmax(hi, axis_name)
    return jnp.sum(occ), lo, hi


def linear_count(filled: int, rows: float,
                 buckets: int = SKETCH_BUCKETS) -> float:
    """Linear-counting NDV from bitmap occupancy, clamped to [0, rows]."""
    if rows <= 0 or filled <= 0:
        return 0.0
    if filled >= buckets:  # saturated sketch: every value looks distinct
        return float(rows)
    ndv = -buckets * math.log1p(-filled / buckets)
    return float(min(max(ndv, 1.0), rows))


def sketch_columns(columns: Mapping[str, jax.Array], valid: jax.Array,
                   names: Sequence[str], axis_name=None):
    """Traced sketch of ``names`` columns under ``valid``: name ->
    (filled, lo, hi). Composable under jit; host wrappers finish it.
    ``axis_name``: merge the per-shard sketches over that mesh axis."""
    return {n: _sketch_one(columns[n], valid, axis_name) for n in names}


def analyze_table(table) -> TableStats:
    """Host-side TableStats of a local :class:`~repro.core.table.Table`
    (the same sweep ``DistContext.analyze`` runs over a global view)."""
    names = tuple(table.key_column_names)
    rows = int(table.row_count)

    sk = jax.jit(lambda cols, valid: sketch_columns(cols, valid, names))(
        {n: table.columns[n] for n in names}, table.valid_mask())
    cols = []
    for n in names:
        filled, lo, hi = sk[n]
        cols.append((n, ColumnStats(linear_count(int(filled), rows),
                                    float(np.asarray(lo)),
                                    float(np.asarray(hi)))))
    return TableStats(rows=float(rows), columns=tuple(cols),
                      max_shard_rows=float(rows))
