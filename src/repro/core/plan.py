"""Lazy logical-plan IR + fusing optimizer: one shard_map program per pipeline.

Cylon's core claim (paper §II) is that relational operators *compose* into a
single efficient distributed program; the follow-up operator-pattern algebra
(arXiv:2209.06146) makes that composition explicit. This module is that
composition layer for the JAX adaptation: a small IR of relational nodes, a
rule-based optimizer, and a compiler that evaluates the whole optimized plan
inside ONE ``shard_map`` body — so a four-operator ETL chain is one XLA
dispatch, not four, with no full-capacity ``DistTable`` materialization
between operators.

Optimizer passes (applied in order by :func:`optimize`):

1. **Predicate column probing** — run each ``Select`` predicate once over
   tiny zero-filled columns behind a recording mapping to learn which
   columns it reads (its pushdown footprint). Predicates that defeat the
   probe are conservatively pinned in place.
2. **Predicate pushdown** — move a ``Select`` below ``Project``/``Sort``/
   ``Repartition`` and into the side of a ``Join`` whose columns it reads
   (inner/left joins push left, inner/right push right), so rows are
   dropped *before* they cross the AllToAll.
3. **Projection pushdown** — insert ``Project`` nodes under every shuffle
   boundary (join/groupby/sort/repartition inputs) keeping only the columns
   the rest of the plan consumes, shrinking bytes/row on the wire.
4. **Shuffle elision** — thread :class:`~repro.core.repartition.Partitioning`
   and :class:`~repro.core.repartition.RangePartitioning` tags bottom-up; an
   input already hash-partitioned on an operator's keys (same seed, same
   modulus) has its AllToAll elided, and a range-partitioned input (sort
   output) satisfies a downstream Sort/GroupBy/Join on a key prefix the
   same way — a join additionally range-ALIGNS its other side to the
   sorted side's boundaries (one AllToAll instead of two). A single-shard
   mesh elides every shuffle (hash to one partition is the identity).
5. **Cost model** (``repro.core.stats``) — per-operator cardinality
   estimators propagate :class:`~repro.core.stats.TableStats` (row
   counts, per-key NDV sketches) from analyzed inputs through the plan;
   the cost pass then (a) resolves each GroupBy's ``strategy="auto"`` to
   ``shuffle`` vs ``two_phase`` by comparing estimated shuffle rows
   (``rows`` vs ``num_shards * key NDV`` — the arXiv:2010.14596
   crossover), (b) right-sizes every unset ``bucket_capacity`` /
   ``out_capacity`` from estimated occupancy instead of the fixed
   ``FALLBACK_SLACK`` multiple of table capacity, and (c) marks those
   nodes ``sized`` so the runtime knows an overflow means *estimate was
   wrong* and triggers one recompile-with-conservative-capacity retry
   (``DistContext._run_plan``) rather than wrong results. Without input
   statistics the pass only resolves ``auto`` strategies (to the
   documented ``two_phase`` fallback) and the executor's
   ``FALLBACK_SLACK`` sizing applies — byte-compatible with the
   pre-cost-model behavior.

``Limit`` is a true global head-n (a counts prefix-scan inside the fused
body assigns each shard its take quota), not a per-shard truncation; the
optimizer pushes it below order-preserving ``Project`` so truncation
happens before wide-row work.

The canonicalized plan (:func:`canonical_key`) is the jit-cache key, so a
pipeline re-collected every training step compiles exactly once.

:func:`execute_plan` traces each operator node under its own
``jax.named_scope`` (:data:`OPERATOR_SCOPES`), so every instruction of the
compiled program names, in its ``op_name`` metadata, the operator that
issued it. Exchanges open ``engine.exchange`` inside their operator
(``ops_dist``) and the row-reorder steps of the local operators open
``engine.step.*`` (``ops_local``). Scopes are metadata only: the compiled
code is the same with or without them.
"""
from __future__ import annotations

import dataclasses
import types
from dataclasses import dataclass, field, replace
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core import ops_agg as A
from repro.core import ops_dist as D
from repro.core import ops_local as L
from repro.core import stats as S
from repro.core.repartition import (Partitioning, RangePartitioning,
                                    default_bucket_capacity,
                                    range_prefix_matches)
from repro.core.table import Table

# ---------------------------------------------------------------------------
# IR nodes
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Node:
    """Base class of plan IR nodes (immutable, structurally comparable)."""


@dataclass(frozen=True)
class Scan(Node):
    """Leaf: the ``slot``-th input DistTable of the compiled program."""

    slot: int
    partitioning: Partitioning | RangePartitioning | None = None


@dataclass(frozen=True)
class Select(Node):
    """Row filter by a user predicate over the columns dict.

    ``key``: user-supplied hashable cache key for the predicate — without
    it the plan cannot be canonicalized and recompiles on every execution
    (the pre-existing eager ``ctx.select`` behaviour, now opt-out).
    ``columns``: the predicate's probed column footprint (filled by the
    optimizer; None = unknown, treat as reading everything).
    """

    child: Node
    predicate: Callable = field(compare=False)
    key: object = None
    columns: tuple[str, ...] | None = None


@dataclass(frozen=True)
class Project(Node):
    child: Node
    columns: tuple[str, ...]


@dataclass(frozen=True)
class Limit(Node):
    """True global head(n): a counts prefix-scan over the shuffle axis
    assigns each shard a take quota summing to min(n, total rows) — the
    first n rows in shard order, i.e. the global top-n after a Sort."""

    child: Node
    n: int


@dataclass(frozen=True)
class Repartition(Node):
    """Explicit hash repartition on ``keys`` — pre-partition once so later
    joins/groupbys on the same keys (and seed) elide their shuffles."""

    child: Node
    keys: tuple[str, ...]
    seed: int = 7
    bucket_capacity: int | None = None
    skip_shuffle: bool = False
    sized: bool = False  # bucket filled in by the cost model (estimate!)
    stages: int | None = None  # shuffle pipeline depth (None = cost pick)
    shuffle_mode: str = "alltoall"


@dataclass(frozen=True)
class Join(Node):
    left: Node
    right: Node
    on: tuple[str, ...]
    how: str = "inner"
    algorithm: str = "sort"
    bucket_capacity: int | None = None
    out_capacity: int | None = None
    seed: int = 7
    shuffle_seed: int | None = None  # resolved by the optimizer
    skip_left_shuffle: bool = False
    skip_right_shuffle: bool = False
    # range fast path (set by the optimizer): the named side is range-
    # partitioned on align_keys (a prefix of `on`); the other side is
    # range-ALIGNED to its boundaries instead of hash-shuffled.
    align: str | None = None          # None | "left" | "right"
    align_keys: tuple[str, ...] | None = None
    sized: bool = False      # bucket filled by the cost model (estimate!)
    out_sized: bool = False  # out_capacity filled by the cost model —
    # tracked separately so a USER-set out_capacity (deliberate
    # truncation, surfaced in stats) is never treated as a bad estimate
    stages: int | None = None  # shuffle pipeline depth (None = cost pick)
    shuffle_mode: str = "alltoall"


@dataclass(frozen=True)
class GroupBy(Node):
    child: Node
    keys: tuple[str, ...]
    pairs: tuple[tuple[str, str], ...]  # normalized (col, op) aggregations
    # "auto" defers the shuffle-vs-two-phase choice to the cost model
    # (arXiv:2010.14596: the winner flips with key cardinality); resolved
    # to a concrete strategy by the cost pass before execution —
    # "two_phase" when no statistics are available.
    strategy: str = "auto"
    bucket_capacity: int | None = None
    partial_capacity: int | None = None
    out_capacity: int | None = None
    seed: int = 7
    shuffle_seed: int | None = None
    skip_shuffle: bool = False
    sized: bool = False  # bucket filled in by the cost model (estimate!)
    out_sized: bool = False  # out/partial capacities from the key NDV, as
    # Join.out_sized: truncation counts toward the overflow retry
    stages: int | None = None  # shuffle pipeline depth (None = cost pick)
    shuffle_mode: str = "alltoall"


@dataclass(frozen=True)
class Sort(Node):
    child: Node
    by: tuple[str, ...]
    bucket_capacity: int | None = None
    samples_per_shard: int = 64
    skip_shuffle: bool = False
    sized: bool = False  # bucket filled in by the cost model (estimate!)
    stages: int | None = None  # shuffle pipeline depth (None = cost pick)
    shuffle_mode: str = "alltoall"


@dataclass(frozen=True)
class Window(Node):
    """Row-preserving window functions over (by, order_by)-sorted segments.

    Lowers to ``ops_dist.dist_window``: range partition on (by + order_by)
    — the dist_sort placement — then per-shard segment scans with a
    boundary-carry all_gather (never an AllToAll). An input already
    range-partitioned on a (by + order_by) prefix (a Sort output, or a
    previous Window) elides the shuffle entirely: the optimizer's prefix
    rules apply exactly as they do to Sort. ``funcs`` is the canonical
    ``ops_agg.normalize_funcs`` tuple.
    """

    child: Node
    by: tuple[str, ...]
    order_by: tuple[str, ...]
    funcs: tuple[tuple, ...]
    bucket_capacity: int | None = None
    samples_per_shard: int = 64
    skip_shuffle: bool = False
    sized: bool = False  # bucket filled in by the cost model (estimate!)
    stages: int | None = None  # shuffle pipeline depth (None = cost pick)
    shuffle_mode: str = "alltoall"


@dataclass(frozen=True)
class SetOp(Node):
    """Shared shape of the whole-row-hash binary operators."""

    left: Node
    right: Node
    bucket_capacity: int | None = None
    seed: int = 7
    mode: str = "symmetric"  # Difference only
    skip_left_shuffle: bool = False
    skip_right_shuffle: bool = False
    sized: bool = False  # bucket filled in by the cost model (estimate!)
    stages: int | None = None  # shuffle pipeline depth (None = cost pick)
    shuffle_mode: str = "alltoall"


@dataclass(frozen=True)
class Union(SetOp):
    pass


@dataclass(frozen=True)
class Intersect(SetOp):
    pass


@dataclass(frozen=True)
class Difference(SetOp):
    pass


@dataclass(frozen=True)
class Distinct(Node):
    child: Node
    bucket_capacity: int | None = None
    seed: int = 7
    skip_shuffle: bool = False
    sized: bool = False  # bucket filled in by the cost model (estimate!)
    stages: int | None = None  # shuffle pipeline depth (None = cost pick)
    shuffle_mode: str = "alltoall"


def children(node: Node) -> tuple[Node, ...]:
    if isinstance(node, Scan):
        return ()
    if isinstance(node, (Join, SetOp)):
        return (node.left, node.right)
    return (node.child,)


def _with_children(node: Node, kids: Sequence[Node]) -> Node:
    if isinstance(node, Scan):
        return node
    if isinstance(node, (Join, SetOp)):
        return replace(node, left=kids[0], right=kids[1])
    return replace(node, child=kids[0])


def remap_scans(node: Node, mapping: dict[int, int]) -> Node:
    """Renumber Scan slots (merging two frames' input lists into one)."""
    if isinstance(node, Scan):
        return replace(node, slot=mapping[node.slot])
    return _with_children(node, [remap_scans(c, mapping)
                                 for c in children(node)])


# ---------------------------------------------------------------------------
# schema inference
# ---------------------------------------------------------------------------

JOIN_SUFFIX = "_r"  # ops_local.join's clash suffix, mirrored here


class _Analysis:
    """Memoized per-node output schema (name -> ShapeDtypeStruct of one row's
    trailing shape). Memo keys are node identities; node refs are held so
    ids cannot be recycled mid-pass."""

    def __init__(self, input_schemas: Sequence[dict]):
        self.inputs = [dict(s) for s in input_schemas]
        self._memo: dict[int, tuple[Node, dict]] = {}

    def schema(self, node: Node) -> dict:
        hit = self._memo.get(id(node))
        if hit is not None and hit[0] is node:
            return hit[1]
        out = self._schema(node)
        self._memo[id(node)] = (node, out)
        return out

    def _schema(self, node: Node) -> dict:
        if isinstance(node, Scan):
            return dict(self.inputs[node.slot])
        if isinstance(node, Project):
            ch = self.schema(node.child)
            return {k: ch[k] for k in node.columns}
        if isinstance(node, Join):
            lsch = self.schema(node.left)
            rsch = self.schema(node.right)
            out = dict(lsch)
            for k, v in rsch.items():
                out[k + JOIN_SUFFIX if k in lsch else k] = v
            return out
        if isinstance(node, GroupBy):
            ch = self.schema(node.child)
            out = {k: ch[k] for k in node.keys}
            f32 = jnp.dtype(jnp.float32)
            for col, op in node.pairs:
                base = ch[col]
                if op in ("mean", "var"):
                    sds = jax.ShapeDtypeStruct(base.shape, f32)
                elif op == "count":
                    sds = jax.ShapeDtypeStruct((), jnp.dtype(jnp.int32))
                else:
                    sds = base
                out[f"{col}_{op}"] = sds
            return out
        if isinstance(node, Window):
            out = dict(self.schema(node.child))
            i32 = jnp.dtype(jnp.int32)
            f32 = jnp.dtype(jnp.float32)
            for fn, col, off in node.funcs:
                name = A.window_output_name(fn, col, off)
                if col is None:  # rank / dense_rank / row_number
                    sds = jax.ShapeDtypeStruct((), i32)
                elif fn == "running_mean":
                    sds = jax.ShapeDtypeStruct((), f32)
                else:  # lag / lead / cumsum / cummax keep the input dtype
                    sds = out[col]
                out[name] = sds
            return out
        # Select / Limit / Sort / Distinct / Repartition / set ops: unchanged
        return dict(self.schema(children(node)[0]))


# ---------------------------------------------------------------------------
# optimizer pass 1: predicate column probing
# ---------------------------------------------------------------------------


class _RecordingColumns(dict):
    """Columns dict that records which names a predicate reads."""

    def __init__(self, cols: dict):
        super().__init__(cols)
        self.accessed: set[str] = set()

    def __getitem__(self, k):
        self.accessed.add(k)
        return super().__getitem__(k)

    def get(self, k, default=None):
        self.accessed.add(k)
        return super().get(k, default)


def probe_predicate(predicate: Callable, schema: dict) -> tuple[str, ...] | None:
    """Learn a predicate's column footprint by running it over zeros.

    Returns the sorted accessed-column tuple, or None when the probe fails
    (exception, or no recorded access — e.g. the predicate iterates the
    dict), which pins the Select in place during pushdown.
    """
    cols = _RecordingColumns({
        k: jnp.zeros((2,) + tuple(s.shape), s.dtype) for k, s in schema.items()
    })
    try:
        out = predicate(cols)
        _ = jnp.shape(out)  # must be array-like
    except Exception:  # noqa: BLE001 — any failure disables pushdown only
        return None
    return tuple(sorted(cols.accessed)) or None


def _annotate_selects(node: Node, an: _Analysis) -> Node:
    kids = [_annotate_selects(c, an) for c in children(node)]
    node = _with_children(node, kids)
    if isinstance(node, Select) and node.columns is None:
        cols = probe_predicate(node.predicate, an.schema(node.child))
        if cols is not None:
            node = replace(node, columns=cols)
    return node


# ---------------------------------------------------------------------------
# optimizer pass 2: predicate pushdown (filter before shuffle)
# ---------------------------------------------------------------------------


def _pushdown_selects(node: Node, an: _Analysis) -> Node:
    kids = [_pushdown_selects(c, an) for c in children(node)]
    node = _with_children(node, kids)
    if not isinstance(node, Select) or node.columns is None:
        return node
    refs = set(node.columns)
    ch = node.child
    if isinstance(ch, Project) and refs <= set(ch.columns):
        return replace(ch, child=_pushdown_selects(
            replace(node, child=ch.child), an))
    if isinstance(ch, (Sort, Repartition)):
        return replace(ch, child=_pushdown_selects(
            replace(node, child=ch.child), an))
    if isinstance(ch, Join):
        lnames = set(an.schema(ch.left))
        rnames = set(an.schema(ch.right))
        # pushing a one-sided filter through an outer join changes which
        # rows of the OTHER side surface as unmatched — only inner/left
        # joins admit a left push, inner/right a right push.
        if refs <= lnames and ch.how in ("inner", "left"):
            return replace(ch, left=_pushdown_selects(
                replace(node, child=ch.left), an))
        if refs <= rnames and not (refs & lnames) and ch.how in ("inner",
                                                                 "right"):
            return replace(ch, right=_pushdown_selects(
                replace(node, child=ch.right), an))
    return node


# ---------------------------------------------------------------------------
# optimizer pass 2b: limit pushdown (truncate before wide-row work)
# ---------------------------------------------------------------------------


def _pushdown_limits(node: Node) -> Node:
    """``Limit(Project(x)) -> Project(Limit(x))``: Project preserves row
    order and count, so the global head-n commutes with it — the take
    quota is computed (and rows dropped) before any wide-row work above.
    Project is the ONLY order-preserving rewrite target: Select changes
    row membership, Sort/Repartition change placement/order."""
    kids = [_pushdown_limits(c) for c in children(node)]
    node = _with_children(node, kids)
    if isinstance(node, Limit) and isinstance(node.child, Project):
        proj = node.child
        return replace(proj, child=_pushdown_limits(
            replace(node, child=proj.child)))
    return node


# ---------------------------------------------------------------------------
# optimizer pass 3: projection pushdown (narrow rows before shuffle)
# ---------------------------------------------------------------------------


def _project_to(child: Node, cols: set[str], an: _Analysis) -> Node:
    """Project ``child`` down to ``cols`` (child-schema order) if narrower."""
    sch = an.schema(child)
    if set(sch) == cols:
        return child
    ordered = tuple(k for k in sch if k in cols)
    if isinstance(child, Project):
        return replace(child, columns=ordered)
    return Project(child, ordered)


def _pushdown_projections(node: Node, needed: set[str] | None,
                          an: _Analysis) -> Node:
    if isinstance(node, Scan):
        return node
    if isinstance(node, Project):
        return replace(node, child=_pushdown_projections(
            node.child, set(node.columns), an))
    if isinstance(node, Select):
        child_needed = (None if (needed is None or node.columns is None)
                        else needed | set(node.columns))
        return replace(node, child=_pushdown_projections(
            node.child, child_needed, an))
    if isinstance(node, Limit):
        return replace(node, child=_pushdown_projections(node.child, needed,
                                                         an))
    if isinstance(node, (Sort, Repartition, Window)):
        if isinstance(node, Sort):
            keys = set(node.by)
        elif isinstance(node, Repartition):
            keys = set(node.keys)
        else:  # Window: partition keys + order keys + function inputs
            keys = set(node.by) | set(node.order_by) \
                | {c for _, c, _ in node.funcs if c is not None}
        cn = None if needed is None else needed | keys
        child = _pushdown_projections(node.child, cn, an)
        if cn is not None:
            # window OUTPUT names in `cn` are not child columns: the
            # intersection with the child schema drops them
            child = _project_to(child, cn & set(an.schema(child)) | keys, an)
        return replace(node, child=child)
    if isinstance(node, Join):
        lsch = an.schema(node.left)
        rsch = an.schema(node.right)
        need_out = set(an.schema(node)) if needed is None else set(needed)
        ln = {k for k in lsch if k in need_out} | set(node.on)
        rn = set(node.on)
        for k in rsch:
            if (k + JOIN_SUFFIX if k in lsch else k) in need_out:
                rn.add(k)
                if k in lsch:
                    # a consumed '<k>_r' only gets its suffix while the
                    # name still CLASHES — keep the left copy alive even
                    # if nothing upstream reads it
                    ln.add(k)
        left = _project_to(_pushdown_projections(node.left, ln, an), ln, an)
        right = _project_to(_pushdown_projections(node.right, rn, an), rn, an)
        return replace(node, left=left, right=right)
    if isinstance(node, GroupBy):
        cn = set(node.keys) | {c for c, _ in node.pairs}
        child = _project_to(_pushdown_projections(node.child, cn, an), cn, an)
        return replace(node, child=child)
    # set ops & distinct compare whole rows: every child column is load-
    # bearing, nothing can be dropped below them.
    kids = [_pushdown_projections(c, None, an) for c in children(node)]
    return _with_children(node, kids)


# ---------------------------------------------------------------------------
# optimizer pass 4: shuffle elision via Partitioning/RangePartitioning tags
# ---------------------------------------------------------------------------


def _range_fp(node: Node):
    """Plan-internal splitter provenance: the canonical form of the subtree
    that computes the splitters. Two structurally identical subtrees in ONE
    plan see the same inputs and are deterministic, so equal fingerprints
    imply equal placement. None (uncanonicalizable subtree) never matches.
    """
    try:
        return ("plan", _canon(node))
    except _Uncacheable:
        return None


def _elide(node: Node, p: int, an: _Analysis
           ) -> tuple[Node, Partitioning | RangePartitioning | None]:
    if isinstance(node, Scan):
        part = node.partitioning
        if part is not None and part.num_partitions != p:
            part = None
        return node, part
    if isinstance(node, Select):
        c, cp = _elide(node.child, p, an)
        return replace(node, child=c), cp
    if isinstance(node, Project):
        c, cp = _elide(node.child, p, an)
        keep = cp if cp is not None and set(cp.keys) <= set(node.columns) \
            else None
        return replace(node, child=c), keep
    if isinstance(node, Limit):
        c, cp = _elide(node.child, p, an)
        return replace(node, child=c), cp
    if isinstance(node, Repartition):
        c, cp = _elide(node.child, p, an)
        target = Partitioning(node.keys, p, node.seed)
        skip = p == 1 or cp == target
        return replace(node, child=c, skip_shuffle=skip), target
    if isinstance(node, Join):
        l, lp = _elide(node.left, p, an)
        r, rp = _elide(node.right, p, an)
        # inner/left outputs keep true key values on their hash shard;
        # right/full emit unmatched-side rows whose (left-sourced) key
        # columns are zero-filled, so NO placement tag survives them.
        inner_ish = node.how in ("inner", "left")

        def out_part(seed):
            if inner_ish:
                return Partitioning(node.on, p, seed)
            return None
        if p == 1:
            out = replace(node, left=l, right=r, skip_left_shuffle=True,
                          skip_right_shuffle=True, shuffle_seed=node.seed)
            return out, out_part(node.seed)
        l_range = range_prefix_matches(lp, node.on)
        r_range = range_prefix_matches(rp, node.on)
        # both sides range-partitioned by the SAME splitter computation:
        # equal keys already colocated everywhere, skip both shuffles
        if l_range and r_range and lp == rp and lp.fingerprint is not None:
            out = replace(node, left=l, right=r, skip_left_shuffle=True,
                          skip_right_shuffle=True, shuffle_seed=node.seed)
            return out, (lp if inner_ish else None)
        target = None
        if isinstance(lp, Partitioning) and lp.keys == node.on:
            target = lp
        elif isinstance(rp, Partitioning) and rp.keys == node.on:
            target = rp
        if target is not None:
            out = replace(node, left=l, right=r,
                          skip_left_shuffle=lp == target,
                          skip_right_shuffle=rp == target,
                          shuffle_seed=target.seed)
            return out, out_part(target.seed)
        # one side range-partitioned (sort output): keep its placement and
        # range-ALIGN the other side to its boundaries — one AllToAll
        # instead of two, and the range placement survives the join
        if l_range:
            out = replace(node, left=l, right=r, skip_left_shuffle=True,
                          align="left", align_keys=lp.keys,
                          shuffle_seed=node.seed)
            return out, (lp if inner_ish else None)
        if r_range:
            out = replace(node, left=l, right=r, skip_right_shuffle=True,
                          align="right", align_keys=rp.keys,
                          shuffle_seed=node.seed)
            return out, (rp if inner_ish else None)
        out = replace(node, left=l, right=r, skip_left_shuffle=False,
                      skip_right_shuffle=False, shuffle_seed=node.seed)
        return out, out_part(node.seed)
    if isinstance(node, GroupBy):
        c, cp = _elide(node.child, p, an)
        # any hash partitioning on exactly the group keys colocates each
        # key on one shard — seed-independent, unlike the join fast path;
        # a range partitioning on a PREFIX of the keys colocates them too
        # (placement is a function of the prefix tuple)
        matches = (isinstance(cp, Partitioning) and cp.keys == node.keys) \
            or range_prefix_matches(cp, node.keys)
        if p == 1 or matches:
            out = replace(node, child=c, skip_shuffle=True,
                          shuffle_seed=node.seed)
            return out, cp if matches else Partitioning(node.keys, p,
                                                        node.seed)
        out = replace(node, child=c, shuffle_seed=node.seed)
        return out, Partitioning(node.keys, p, node.seed)
    if isinstance(node, Sort):
        c, cp = _elide(node.child, p, an)
        # an input range-partitioned on a by-prefix (equal prefixes
        # colocated, shard ranges ordered) — or on an EXTENSION of `by`
        # (placement refines the requested order) — already has the right
        # global placement: a local sort alone yields the global order,
        # and the input's placement tag survives untouched
        el = range_prefix_matches(cp, node.by) or (
            isinstance(cp, RangePartitioning)
            and node.by == cp.keys[:len(node.by)])
        if el:
            return replace(node, child=c, skip_shuffle=True), cp
        out = replace(node, child=c, skip_shuffle=p == 1)
        # the shuffle (or the single-shard identity) leaves the output
        # range-partitioned on `by`; fingerprint = the producing subtree
        return out, RangePartitioning(node.by, p, _range_fp(out))
    if isinstance(node, Window):
        c, cp = _elide(node.child, p, an)
        keys = node.by + node.order_by
        # same placement rules as Sort: a range partitioning on a prefix
        # of (by + order_by) — or an extension of it — already gives every
        # shard a contiguous slice of the target global order, so the
        # window pays only its boundary all_gather; the input's placement
        # tag survives (windows are row- and placement-preserving)
        el = range_prefix_matches(cp, keys) or (
            isinstance(cp, RangePartitioning)
            and keys == cp.keys[:len(keys)])
        if el:
            return replace(node, child=c, skip_shuffle=True), cp
        out = replace(node, child=c, skip_shuffle=p == 1)
        return out, RangePartitioning(keys, p, _range_fp(out))
    if isinstance(node, SetOp):
        l, lp = _elide(node.left, p, an)
        r, rp = _elide(node.right, p, an)
        keys = tuple(sorted(an.schema(node.left)))  # whole-row hash order
        if p == 1:
            out = replace(node, left=l, right=r, skip_left_shuffle=True,
                          skip_right_shuffle=True)
            return out, Partitioning(keys, p, node.seed)
        target = None
        if isinstance(lp, Partitioning) and lp.keys == keys:
            target = lp
        elif isinstance(rp, Partitioning) and rp.keys == keys:
            target = rp
        elided_seed = target.seed if target is not None else node.seed
        if target is None:
            target = Partitioning(keys, p, node.seed)
        out = replace(node, left=l, right=r, seed=elided_seed,
                      skip_left_shuffle=lp == target,
                      skip_right_shuffle=rp == target)
        return out, Partitioning(keys, p, elided_seed)
    if isinstance(node, Distinct):
        c, cp = _elide(node.child, p, an)
        keys = tuple(sorted(an.schema(node.child)))
        # hash on exactly the whole row (seed-independent) colocates
        # duplicates; so does ANY range partitioning — its keys are a
        # subset of the row, and equal rows have equal key tuples
        matches = (isinstance(cp, Partitioning) and cp.keys == keys) \
            or isinstance(cp, RangePartitioning)
        skip = p == 1 or matches
        part = cp if matches else Partitioning(keys, p, node.seed)
        return replace(node, child=c, skip_shuffle=skip), part
    raise TypeError(node)


# ---------------------------------------------------------------------------
# optimizer pass 5: the cost model — cardinality estimation + sizing
# ---------------------------------------------------------------------------


class _Estimator:
    """Memoized per-node :class:`~repro.core.stats.TableStats` estimate.

    None = unknown (an input without statistics poisons everything above
    it — the conservative fixed-slack path then applies). Estimates are
    classic System-R style: default selectivity for predicates, NDV-capped
    output rows for GroupBy/Distinct, containment for joins.
    """

    def __init__(self, an: _Analysis, input_stats: Sequence):
        self.an = an
        self.inputs = list(input_stats)
        self._memo: dict[int, tuple[Node, object]] = {}

    def stats(self, node: Node) -> S.TableStats | None:
        hit = self._memo.get(id(node))
        if hit is not None and hit[0] is node:
            return hit[1]
        out = self._stats(node)
        self._memo[id(node)] = (node, out)
        return out

    def _stats(self, node: Node) -> S.TableStats | None:
        if isinstance(node, Scan):
            if node.slot >= len(self.inputs):
                return None
            return self.inputs[node.slot]
        kids = [self.stats(c) for c in children(node)]
        if isinstance(node, Select):
            cs = kids[0]
            return None if cs is None else S.cap_rows(
                cs, cs.rows * S.DEFAULT_SELECTIVITY)
        if isinstance(node, Project):
            cs = kids[0]
            return None if cs is None else S.cap_rows(cs, cs.rows,
                                                      keep=node.columns)
        if isinstance(node, Limit):
            cs = kids[0]
            return None if cs is None else S.cap_rows(
                cs, min(float(node.n), cs.rows))
        if isinstance(node, (Sort, Repartition, Window)):
            # row- and key-preserving; only the shard placement changes
            # (a Window appends result columns, which simply carry no
            # column statistics — they never drive placement)
            cs = kids[0]
            return None if cs is None else S.cap_rows(cs, cs.rows)
        if isinstance(node, GroupBy):
            cs = kids[0]
            if cs is None:
                return None
            ndv = cs.ndv(node.keys)
            rows = cs.rows if ndv is None else min(ndv, cs.rows)
            return S.cap_rows(cs, rows, keep=node.keys)
        if isinstance(node, Join):
            sl, sr = kids
            if sl is None or sr is None:
                return None
            # containment: every value of the smaller key domain joins
            # into the larger -> |L><R| = |L|*|R| / max(ndv_l, ndv_r)
            dl = sl.ndv(node.on)
            dr = sr.ndv(node.on)
            dl = sl.rows if dl is None else dl
            dr = sr.rows if dr is None else dr
            m = sl.rows * sr.rows / max(dl, dr, 1.0)
            rows = {"inner": m, "left": m + sl.rows, "right": m + sr.rows,
                    "full": m + sl.rows + sr.rows}[node.how]
            lsch = self.an.schema(node.left)
            cols = dict(sl.columns)
            for k, c in sr.columns:
                cols[k + JOIN_SUFFIX if k in lsch else k] = c
            for k in node.on:  # equi-key: the smaller NDV survives
                a, b = sl.col(k), sr.col(k)
                if a is not None and b is not None:
                    cols[k] = S.ColumnStats(min(a.ndv, b.ndv), a.lo, a.hi)
            return S.cap_rows(
                S.TableStats(rows=rows, columns=tuple(sorted(cols.items()))),
                rows)
        if isinstance(node, (Union, Intersect, Difference)):
            sl, sr = kids
            if sl is None or sr is None:
                return None
            if isinstance(node, Intersect):
                rows = min(sl.rows, sr.rows)
            elif isinstance(node, Difference) and node.mode == "left":
                rows = sl.rows
            else:  # union / symmetric difference upper bound
                rows = sl.rows + sr.rows
            return S.cap_rows(sl, rows)
        if isinstance(node, Distinct):
            cs = kids[0]
            if cs is None:
                return None
            ndv = cs.ndv(tuple(self.an.schema(node.child)))
            rows = cs.rows if ndv is None else min(ndv, cs.rows)
            return S.cap_rows(cs, rows)
        raise TypeError(node)


def _schema_row_bytes(schema: dict) -> int:
    """Dense wire bytes per row of a schema (the _row_bytes formula on
    ShapeDtypeStructs of trailing row shapes)."""
    total = 0
    for sds in schema.values():
        n = 1
        for d in sds.shape:
            n *= d
        total += n * jnp.dtype(sds.dtype).itemsize
    return total


def _pick_node_stages(node: Node, est: _Estimator, p: int, bucket,
                      skipped: bool, *sources: Node):
    """The cost pass's shuffle-staging pick: wire bytes from the sized
    bucket and the shuffled input's schema -> :func:`S.pick_stages`.
    Keeps an explicit ``stages=`` untouched; leaves None (runtime
    auto-pick from the same formula) when the bucket isn't known yet."""
    if node.stages is not None or bucket is None or p <= 1 or skipped:
        return node.stages
    rb = max(_schema_row_bytes(est.an.schema(s)) for s in sources)
    return S.pick_stages(p * p * bucket * rb, bucket)


def _size_groupby(node: GroupBy, strategy: str, cs: S.TableStats,
                  ndv: float, p: int) -> dict:
    """Output slots of a groupby from its key NDV, on every mesh size.

    A shard that groups its own rows holds at most ``min(ndv, its rows)``
    groups; after an exchange the keys are hashed over ``p`` shards. Each
    budget takes the join's margin (``S.with_skew_margin`` /
    ``S.size_output``) and is marked ``out_sized``: the executor counts
    the groups past it, so an underestimate takes the safe retry. A
    capacity the user set is kept as it is, with its truncation contract.
    """
    local = S.with_skew_margin(min(ndv, cs.shard_rows(p)))
    if p == 1 or node.skip_shuffle:
        return {"out_capacity": local, "out_sized": True}
    out = {"out_capacity": S.size_output(ndv, p), "out_sized": True}
    if strategy == "two_phase":
        out["partial_capacity"] = local
    return out


def _apply_costs(node: Node, est: _Estimator, p: int) -> Node:
    """Fill unset capacities / resolve ``auto`` strategies from estimates.

    Every capacity this pass writes is marked ``sized``/``out_sized`` on
    its node: the runtime treats overflow on a sized plan as "the estimate
    was wrong" and retries once with conservative capacities
    (``execute_plan(..., safe_capacity=True)``). A single-shard mesh
    sizes no exchange bucket and no join output — there is no wire to
    save and the fallback capacities are already local-only — but does
    size a groupby's output from its key NDV (:func:`_size_groupby`),
    which keeps a low-cardinality groupby within the segment kernel. The
    same pass picks each shuffle's pipeline depth (``stages``) from its
    estimated wire bytes — S=1 below the threshold, so small shuffles pay
    zero extra collectives.
    """
    kids = [_apply_costs(c, est, p) for c in children(node)]
    if isinstance(node, GroupBy):
        cs = est.stats(node.child)  # memo holds the pre-costing child
        strategy, bucket, sized = node.strategy, node.bucket_capacity, \
            node.sized
        # None = key cardinality unknown (no stats, or the key column was
        # never sketched — e.g. a derived aggregate column)
        ndv = cs.ndv(node.keys) if cs is not None else None
        if strategy == "auto":
            # two-phase ships <= min(ndv, rows/p) partial rows per shard
            # (p * ndv total); raw shuffle ships every row — pick the
            # smaller wire volume. Missing information (no stats, or the
            # key column was never sketched) takes the documented
            # two_phase fallback, never worst-case shuffle.
            strategy = "two_phase" if ndv is None or p * ndv <= cs.rows \
                else "shuffle"
        if (bucket is None and cs is not None and p > 1
                and not node.skip_shuffle):
            src = cs.shard_rows(p)
            if strategy == "two_phase" and ndv is not None:
                src = min(src, ndv)
            bucket = S.size_bucket(src, p)
            sized = True
        stages = _pick_node_stages(node, est, p, bucket, node.skip_shuffle,
                                   node.child)
        sizes = {}
        if (node.out_capacity is None and node.partial_capacity is None
                and ndv is not None):
            sizes = _size_groupby(node, strategy, cs, ndv, p)
        return replace(node, child=kids[0], strategy=strategy,
                       bucket_capacity=bucket, sized=sized, stages=stages,
                       **sizes)
    if isinstance(node, Repartition):
        cs = est.stats(node.child)
        bucket, sized = node.bucket_capacity, node.sized
        if (bucket is None and cs is not None and p > 1
                and not node.skip_shuffle):
            bucket = S.size_bucket(cs.shard_rows(p), p)
            sized = True
        stages = _pick_node_stages(node, est, p, bucket, node.skip_shuffle,
                                   node.child)
        return replace(node, child=kids[0], bucket_capacity=bucket,
                       sized=sized, stages=stages)
    if isinstance(node, (Sort, Window)):
        cs = est.stats(node.child)
        bucket, sized = node.bucket_capacity, node.sized
        if (bucket is None and cs is not None and p > 1
                and not node.skip_shuffle):
            # sampled splitters miss true quantiles: widen the mean
            bucket = S.size_bucket(cs.shard_rows(p), p,
                                   factor=S.RANGE_SIZING_FACTOR)
            sized = True
        stages = _pick_node_stages(node, est, p, bucket, node.skip_shuffle,
                                   node.child)
        return replace(node, child=kids[0], bucket_capacity=bucket,
                       sized=sized, stages=stages)
    if isinstance(node, Join):
        sl, sr = est.stats(node.left), est.stats(node.right)
        js = est.stats(node)
        bucket, out = node.bucket_capacity, node.out_capacity
        sized, out_sized = node.sized, node.out_sized
        both_skipped = node.skip_left_shuffle and node.skip_right_shuffle
        if p > 1 and sl is not None and sr is not None:
            # a range-ALIGNED join keeps its runtime capacity-bump bucket
            # (a whole source shard may target one anchor range — the
            # unoverflowable bound beats any estimate there)
            if bucket is None and node.align is None and not both_skipped:
                src = max(
                    0.0 if node.skip_left_shuffle else sl.shard_rows(p),
                    0.0 if node.skip_right_shuffle else sr.shard_rows(p))
                bucket = S.size_bucket(src, p)
                sized = True
            if out is None and js is not None:
                # sized by estimated match count, not c_l + c_r — the
                # join truncation counter makes an underestimate loud
                out = S.size_output(js.rows, p,
                                    factor=S.JOIN_OUT_SIZING_FACTOR)
                out_sized = True
        stages = _pick_node_stages(node, est, p, bucket, both_skipped,
                                   node.left, node.right)
        return replace(node, left=kids[0], right=kids[1],
                       bucket_capacity=bucket, out_capacity=out,
                       sized=sized, out_sized=out_sized, stages=stages)
    if isinstance(node, SetOp):
        sl, sr = est.stats(node.left), est.stats(node.right)
        bucket, sized = node.bucket_capacity, node.sized
        both_skipped = node.skip_left_shuffle and node.skip_right_shuffle
        if (bucket is None and p > 1 and sl is not None and sr is not None
                and not both_skipped):
            src = max(0.0 if node.skip_left_shuffle else sl.shard_rows(p),
                      0.0 if node.skip_right_shuffle else sr.shard_rows(p))
            bucket = S.size_bucket(src, p)
            sized = True
        stages = _pick_node_stages(node, est, p, bucket, both_skipped,
                                   node.left, node.right)
        return replace(node, left=kids[0], right=kids[1],
                       bucket_capacity=bucket, sized=sized, stages=stages)
    if isinstance(node, Distinct):
        cs = est.stats(node.child)
        bucket, sized = node.bucket_capacity, node.sized
        if (bucket is None and cs is not None and p > 1
                and not node.skip_shuffle):
            bucket = S.size_bucket(cs.shard_rows(p), p)
            sized = True
        stages = _pick_node_stages(node, est, p, bucket, node.skip_shuffle,
                                   node.child)
        return replace(node, child=kids[0], bucket_capacity=bucket,
                       sized=sized, stages=stages)
    return _with_children(node, kids)


def apply_cost_model(plan: Node, input_schemas: Sequence[dict],
                     num_shards: int, input_stats: Sequence | None = None
                     ) -> Node:
    """The cost pass alone (strategy resolution + capacity sizing) — the
    eager one-node-plan path runs this without the logical rewrites so
    ``ctx.groupby(analyzed_table, ...)`` right-sizes like a fused plan."""
    an = _Analysis(input_schemas)
    est = _Estimator(an, input_stats if input_stats is not None
                     else [None] * len(input_schemas))
    return _apply_costs(plan, est, num_shards)


def estimate_output_stats(plan: Node, input_schemas: Sequence[dict],
                          input_stats: Sequence | None
                          ) -> S.TableStats | None:
    """The estimator's TableStats for the plan's result (None = unknown).
    Attached to materialized DistTables so chained pipelines keep cost-
    model coverage without re-analyzing intermediates."""
    if input_stats is None or not any(s is not None for s in input_stats):
        return None
    an = _Analysis(input_schemas)
    return _Estimator(an, input_stats).stats(plan)


def _node_cost_sized(node: Node) -> bool:
    return getattr(node, "sized", False) or getattr(node, "out_sized", False)


def degrade_shuffles(plan: Node) -> Node:
    """The ``mono-shuffle`` recovery rung: the same plan with every
    exchange pinned to one monolithic AllToAll (``stages=1``, no ring) —
    bit-identical results by the staging contract, but none of the
    pipelined-chunk machinery a ``shuffle.chunk`` fault lives in.
    ``stages=None`` (cost pick) is pinned too: the degraded run must not
    re-pick a staged depth."""
    node = _with_children(plan, [degrade_shuffles(c)
                                 for c in children(plan)])
    names = {f.name for f in dataclasses.fields(node)}
    upd = {}
    if "stages" in names and node.stages != 1:
        upd["stages"] = 1
    if "shuffle_mode" in names and node.shuffle_mode != "alltoall":
        upd["shuffle_mode"] = "alltoall"
    return replace(node, **upd) if upd else node


def plan_cost_sized(plan: Node) -> bool:
    """True when any capacity in the plan came from a cardinality
    ESTIMATE — the signal that runtime overflow warrants the safe retry."""
    if _node_cost_sized(plan):
        return True
    return any(plan_cost_sized(c) for c in children(plan))


def _stats_arity(node: Node) -> int:
    """How many ShuffleStats entries ``execute_plan`` emits for ``node``."""
    if isinstance(node, (Join, SetOp)):
        return 2
    if isinstance(node, (Limit, Repartition, GroupBy, Sort, Window,
                         Distinct)):
        return 1
    return 0


def cost_sized_stats_mask(plan: Node) -> list[bool]:
    """Per-ShuffleStats flag: did THIS entry's capacities come from cost-
    model estimates? Mirrors ``execute_plan``'s depth-first post-order
    stats emission exactly (children left-to-right, then the node's own
    entries), so the overflow-retry gate can ignore overflow on USER-set
    capacities — those keep the pre-cost-model surface-in-stats contract.
    """
    mask: list[bool] = []

    def walk(node: Node):
        for c in children(node):
            walk(c)
        mask.extend([_node_cost_sized(node)] * _stats_arity(node))

    walk(plan)
    return mask


def optimize_with_partitioning(
        plan: Node, input_schemas: Sequence[dict], num_shards: int,
        input_stats: Sequence | None = None, *,
        verify: bool | None = None,
) -> tuple[Node, Partitioning | RangePartitioning | None]:
    """All passes: probe -> predicate pushdown -> limit pushdown ->
    projection pushdown -> shuffle elision -> cost model. Pure
    plan-to-plan; safe to golden-test offline. Also returns the result's
    static placement (one elision walk serves both the rewrite and the
    output DistTable tag).

    ``verify`` runs ``repro.core.verify`` over the (logical, optimized)
    pair and raises ``PlanVerificationError`` on any invariant violation;
    ``None`` defers to the ``REPRO_VERIFY_PLANS`` env gate (default-on
    under pytest). The verifier re-optimizes with ``verify=False`` for
    its idempotence rule, so this never recurses."""
    logical = plan
    an = _Analysis(input_schemas)
    plan = _annotate_selects(plan, an)
    plan = _pushdown_selects(plan, an)
    plan = _pushdown_limits(plan)
    plan = _pushdown_projections(plan, None, an)
    plan, part = _elide(plan, num_shards, an)
    est = _Estimator(an, input_stats if input_stats is not None
                     else [None] * len(input_schemas))
    plan = _apply_costs(plan, est, num_shards)
    if verify is None or verify:
        from repro.core import verify as V  # deferred: verify imports us

        if verify or V.verification_enabled():
            V.verify_or_raise(logical, plan, input_schemas, num_shards,
                              input_stats)
    return plan, part


def optimize(plan: Node, input_schemas: Sequence[dict], num_shards: int,
             input_stats: Sequence | None = None, *,
             verify: bool | None = None) -> Node:
    return optimize_with_partitioning(plan, input_schemas, num_shards,
                                      input_stats, verify=verify)[0]


def output_partitioning(plan: Node, input_schemas: Sequence[dict],
                        num_shards: int
                        ) -> Partitioning | RangePartitioning | None:
    """Static placement of the plan's result (tags the output DistTable)."""
    _, part = _elide(plan, num_shards, _Analysis(input_schemas))
    return part


# ---------------------------------------------------------------------------
# canonical cache key
# ---------------------------------------------------------------------------


class _Uncacheable(Exception):
    pass


def canonical_key(plan: Node):
    """Hashable canonical form of the plan (the jit-cache key), or None when
    any Select lacks a user cache key (callables cannot be canonicalized)."""
    try:
        return _canon(plan)
    except _Uncacheable:
        return None


def identity_key(plan: Node):
    """Fallback cache key for plans :func:`canonical_key` rejects: keyless
    predicates are keyed by the CONTENT of everything that parameterizes
    their behavior, or the plan is not cached at all. Returns the hashable
    key, or ``None`` when any keyless callable cannot be safely
    content-keyed — such plans are never cached and re-trace on every
    dispatch (the pre-cache semantics: always correct, just slower).

    The key embeds the predicate's ``__code__`` object (CPython compares
    code objects by content, so a lambda re-created on every pass through
    its definition site — the common serving pattern — still hits) plus
    the *values* of its captured closure cells, ``__defaults__``,
    ``__kwdefaults__``, and every global its code references (recursively
    through nested code objects). Cache lookup compares these values by
    ``==``, and the cache's key tuple strongly pins them, so:

    * rebinding a module-level global the predicate reads changes the key
      (miss -> recompile with the new value);
    * a captured or referenced UNHASHABLE value (list, dict, ndarray —
      anything mutable-by-design) makes the plan uncacheable;
    * a dead value's id can never be recycled into a false hit (the key
      itself keeps it alive while the entry is resident).

    REMAINING ALIASING HAZARD (the documented contract): a captured
    object whose ``__hash__``/``__eq__`` are identity-based (the
    ``object`` defaults) but which carries mutable state compares equal
    to itself after in-place mutation — the cache cannot see such
    mutation and will reuse the executable traced with the old state.
    Plain values (numbers, strings, tuples, frozen dataclasses) are
    always safe; predicates closing over mutable identity-hashed objects
    must either mutate by REBINDING (which changes the key) or use an
    explicit user ``key=`` covering the state.
    """
    try:
        return _canon(plan, identity=True)
    except _Uncacheable:
        return None


def _value_token(v):
    """Content token for a value a keyless predicate's behavior depends on
    (closure cell, default, referenced global). The value itself rides in
    the key — equality is by content for hashable values; unhashable
    values (the mutable-in-place hazard class) reject caching."""
    try:
        hash(v)
    except TypeError:
        raise _Uncacheable from None
    return (type(v), v)


def _referenced_names(code) -> set:
    """Every name ``code`` (or a code object nested in its constants —
    inner lambdas, pre-3.12 comprehensions) can look up as a global.
    Over-approximate: ``co_names`` also holds attribute names, which at
    worst add spurious key components, never a false hit."""
    names = set(code.co_names)
    for const in code.co_consts:
        if isinstance(const, types.CodeType):
            names |= _referenced_names(const)
    return names


def _identity_of(predicate):
    """Hashable behavior-content of a keyless callable (see
    :func:`identity_key`); raises :class:`_Uncacheable` for opaque
    callables (no ``__code__``) and unhashable parameter values."""
    code = getattr(predicate, "__code__", None)
    if code is None:  # opaque callable: no visible behavior content
        raise _Uncacheable
    try:
        cells = tuple(_value_token(c.cell_contents)
                      for c in getattr(predicate, "__closure__", None) or ())
    except ValueError:  # unfilled cell (self-referential def)
        raise _Uncacheable from None
    defaults = tuple(_value_token(d)
                     for d in getattr(predicate, "__defaults__", None) or ())
    kwdefaults = tuple(
        (n, _value_token(v)) for n, v in
        sorted((getattr(predicate, "__kwdefaults__", None) or {}).items()))
    gl = getattr(predicate, "__globals__", None) or {}
    globals_used = tuple(
        (n, _value_token(gl[n])) if n in gl else (n, "@absent")
        for n in sorted(_referenced_names(code)))
    return ("@code", code, cells, defaults, kwdefaults, globals_used)


def _predicate_fingerprint(predicate):
    """Best-effort structural identity of a predicate's code: a fresh
    lambda with identical source shares it (cache hit), while two
    predicates accidentally given the same user key but different logic
    diverge. Captured closure VALUES are invisible here — the user key
    must cover those (the documented contract)."""
    code = getattr(predicate, "__code__", None)
    if code is None:
        return None
    return (code.co_code, tuple(map(str, code.co_consts)), code.co_names)


def _canon(node: Node, identity: bool = False):
    name = type(node).__name__
    if isinstance(node, Scan):
        return (name, node.slot)
    if isinstance(node, Select):
        if node.key is None:
            if not identity:
                raise _Uncacheable
            key = _identity_of(node.predicate)
        else:
            key = node.key
        return (name, key, _predicate_fingerprint(node.predicate),
                node.columns, _canon(node.child, identity))
    vals = []
    for f in dataclasses.fields(node):
        v = getattr(node, f.name)
        if isinstance(v, Node) or callable(v):
            continue
        # staging knobs at their identity values keep the pre-staging
        # canonical key: S=1 IS today's program (bit-identical, same HLO),
        # so default plans must hit the same cache entries they always did
        if f.name == "stages" and v in (None, 1):
            continue
        if f.name == "shuffle_mode" and v == "alltoall":
            continue
        vals.append((f.name, v))
    return (name, tuple(vals)) + tuple(_canon(c, identity)
                                       for c in children(node))


# ---------------------------------------------------------------------------
# compiler / executor — runs INSIDE shard_map (one body for the whole plan)
# ---------------------------------------------------------------------------


#: the ``jax.named_scope`` each operator node's own work is traced under;
#: nodes not listed (Scan, Project, Repartition) issue no work of their own
#: beyond their exchange, which is scoped ``engine.exchange``
OPERATOR_SCOPES = {Select: "engine.filter", Join: "engine.join",
                   GroupBy: "engine.groupby", Sort: "engine.sort",
                   Window: "engine.window", SetOp: "engine.setop",
                   Distinct: "engine.distinct", Limit: "engine.limit"}


def _operator_scope(node: Node) -> str | None:
    for cls, name in OPERATOR_SCOPES.items():
        if isinstance(node, cls):
            return name
    return None


def execute_plan(plan: Node, tables: Sequence[Table], *, axis_name: str,
                 num_shards: int, report: list | None = None,
                 safe_capacity: bool = False) -> tuple[Table, tuple]:
    """Evaluate the plan over per-shard local Tables.

    Returns ``(output table, stats)`` where ``stats`` is one ShuffleStats
    per *potential* shuffle in depth-first plan order (zeros when elided),
    keeping the stats pytree stable whether or not the optimizer fired.

    ``safe_capacity`` is the overflow-retry mode: every capacity the plan
    left unset is taken at the UNOVERFLOWABLE bound (a send bucket the
    size of the whole source table — no hash spread can exceed it)
    instead of the ``FALLBACK_SLACK`` heuristic. ``DistContext._run_plan``
    re-runs a cost-sized plan this way (with its estimate-derived
    capacities stripped) after the overflow counter proves an estimate
    wrong; capacities the USER set explicitly are honored as-is in both
    modes (their overflow surfaces in stats, the pre-existing contract).
    """
    p = num_shards
    stats: list = []
    memo: dict[int, Table] = {}

    def cap(t: Table, bucket: int | None,
            slack: float = S.FALLBACK_SLACK) -> int:
        if bucket is not None:
            return bucket
        if safe_capacity:
            return t.capacity
        return default_bucket_capacity(t.capacity, p, slack)

    def run(node: Node) -> Table:
        hit = memo.get(id(node))
        if hit is not None:
            return hit
        scope = _operator_scope(node)
        if scope is None:
            out = _exec(node)
        else:
            # inputs first, so one operator's scope never wraps another's
            for kid in children(node):
                run(kid)
            with jax.named_scope(scope):
                out = _exec(node)
        memo[id(node)] = out
        return out

    def _exec(node: Node) -> Table:
        if isinstance(node, Scan):
            return tables[node.slot]
        if isinstance(node, Select):
            return L.select(run(node.child), node.predicate)
        if isinstance(node, Project):
            return L.project(run(node.child), list(node.columns))
        if isinstance(node, Limit):
            t = run(node.child)
            out, st = D.dist_limit(t, node.n, axis_name=axis_name,
                                   report=report)
            stats.extend(st)
            return out
        if isinstance(node, Repartition):
            t = run(node.child)
            out, st = D.dist_repartition_by(
                t, list(node.keys), axis_name=axis_name,
                bucket_capacity=cap(t, node.bucket_capacity), seed=node.seed,
                skip_shuffle=node.skip_shuffle, report=report,
                stages=node.stages, shuffle_mode=node.shuffle_mode)
            stats.extend(st)
            return out
        if isinstance(node, Join):
            lt, rt = run(node.left), run(node.right)
            cb = node.bucket_capacity or max(
                cap(lt, None), cap(rt, None))
            if node.bucket_capacity is None and node.align is not None:
                # range alignment is skew-prone in a way hash is not: ALL
                # of a source shard's rows may target one anchor range. A
                # bucket covering the shuffled side's whole capacity makes
                # a one-destination pileup unoverflowable (the same sizing
                # data/pipeline.py uses by hand); hash defaults would drop
                # rows silently under key skew.
                shuffled = rt if node.align == "left" else lt
                cb = max(cb, shuffled.capacity)
            # default output budget = what a fully-shuffled join would get
            # (each operand lands at p*cb rows after repartition), so an
            # elided shuffle never shrinks the truncation budget relative
            # to the eager chain
            out_capacity = node.out_capacity
            if out_capacity is None:
                out_capacity = int(S.JOIN_OUT_FACTOR * p * cb)
            out, st = D.dist_join(
                lt, rt, list(node.on), axis_name=axis_name,
                bucket_capacity=cb, how=node.how, algorithm=node.algorithm,
                out_capacity=out_capacity, seed=node.seed,
                shuffle_seed=node.shuffle_seed,
                skip_left_shuffle=node.skip_left_shuffle,
                skip_right_shuffle=node.skip_right_shuffle,
                align=node.align, align_keys=node.align_keys,
                count_truncation=node.out_sized,
                report=report, stages=node.stages,
                shuffle_mode=node.shuffle_mode)
            stats.extend(st)
            return out
        if isinstance(node, GroupBy):
            t = run(node.child)
            # "auto" is resolved by the cost pass; a plan executed without
            # it (direct execute_plan callers) gets the documented fallback
            strategy = "two_phase" if node.strategy == "auto" \
                else node.strategy
            out, st = D.dist_groupby(
                t, list(node.keys), node.pairs, axis_name=axis_name,
                bucket_capacity=cap(t, node.bucket_capacity),
                strategy=strategy,
                partial_capacity=node.partial_capacity,
                out_capacity=node.out_capacity, seed=node.seed,
                shuffle_seed=node.shuffle_seed,
                skip_shuffle=node.skip_shuffle,
                count_truncation=node.out_sized, report=report,
                stages=node.stages, shuffle_mode=node.shuffle_mode)
            stats.extend(st)
            return out
        if isinstance(node, Sort):
            t = run(node.child)
            out, st = D.dist_sort(
                t, list(node.by), axis_name=axis_name,
                # range partition by sampled splitters misses true
                # quantiles: the no-stats bucket widens the one fallback
                # constant by the documented sort factor (== the old 4.0)
                bucket_capacity=cap(t, node.bucket_capacity,
                                    slack=S.FALLBACK_SLACK
                                    * S.SORT_SLACK_FACTOR),
                samples_per_shard=node.samples_per_shard,
                skip_shuffle=node.skip_shuffle, report=report,
                stages=node.stages, shuffle_mode=node.shuffle_mode)
            stats.extend(st)
            return out
        if isinstance(node, Window):
            t = run(node.child)
            out, st = D.dist_window(
                t, list(node.by), node.funcs, axis_name=axis_name,
                order_by=list(node.order_by),
                # range partition by sampled splitters, like Sort: the
                # no-stats bucket widens by the documented sort factor
                bucket_capacity=cap(t, node.bucket_capacity,
                                    slack=S.FALLBACK_SLACK
                                    * S.SORT_SLACK_FACTOR),
                samples_per_shard=node.samples_per_shard,
                skip_shuffle=node.skip_shuffle, report=report,
                stages=node.stages, shuffle_mode=node.shuffle_mode)
            stats.extend(st)
            return out
        if isinstance(node, SetOp):
            a, b = run(node.left), run(node.right)
            cb = node.bucket_capacity or max(cap(a, None), cap(b, None))
            kw = dict(axis_name=axis_name, bucket_capacity=cb, seed=node.seed,
                      skip_left_shuffle=node.skip_left_shuffle,
                      skip_right_shuffle=node.skip_right_shuffle,
                      report=report, stages=node.stages,
                      shuffle_mode=node.shuffle_mode)
            if isinstance(node, Union):
                out, st = D.dist_union(a, b, **kw)
            elif isinstance(node, Intersect):
                out, st = D.dist_intersect(a, b, **kw)
            else:
                out, st = D.dist_difference(a, b, mode=node.mode, **kw)
            stats.extend(st)
            return out
        if isinstance(node, Distinct):
            t = run(node.child)
            out, st = D.dist_distinct(
                t, axis_name=axis_name,
                bucket_capacity=cap(t, node.bucket_capacity), seed=node.seed,
                skip_shuffle=node.skip_shuffle, report=report,
                stages=node.stages, shuffle_mode=node.shuffle_mode)
            stats.extend(st)
            return out
        raise TypeError(node)

    out = run(plan)
    return out, tuple(stats)


# ---------------------------------------------------------------------------
# explain
# ---------------------------------------------------------------------------


def _shuffle_word(skip: bool) -> str:
    return "elided" if skip else "alltoall"


def _recovery_rungs(node: Node) -> list[str]:
    """The degradation rungs that apply to ``node`` should its execution
    fail — the ``recovery=`` annotation in :func:`explain`."""
    rungs = []
    if isinstance(node, (Join, SetOp)):
        live = not (node.skip_left_shuffle and node.skip_right_shuffle)
    else:
        live = not getattr(node, "skip_shuffle", True)
    if live and any(f.name == "stages" for f in dataclasses.fields(node)):
        rungs.append("mono-alltoall")
    if isinstance(node, (GroupBy, Window)):
        rungs.append("oracle-kernel")
    if _node_cost_sized(node):
        rungs.append("safe-capacity")
    return rungs


def explain(plan: Node, input_schemas: Sequence[dict] | None = None,
            input_stats: Sequence | None = None, *,
            recovery: bool = False) -> str:
    """Human-readable plan tree (golden-testable): one node per line, with
    every potential shuffle marked ``alltoall`` or ``elided``.

    With ``input_schemas`` + ``input_stats`` every node is additionally
    annotated with its estimated output rows (``~rows=``), and nodes
    whose capacities the cost model filled in show them (``bucket=``,
    ``out=``, ``cost-sized``) — the audit trail for every physical-
    planning decision. Without statistics the output is unchanged.

    ``recovery=True`` appends each node's applicable degradation rungs
    (``recovery=mono-alltoall+oracle-kernel+safe-capacity``) — how the
    retry ladder would re-execute the node after a failure (see
    ``repro.core.faults``). Off by default so golden plans are stable.
    """
    est = None
    if input_schemas is not None and input_stats is not None \
            and any(s is not None for s in input_stats):
        est = _Estimator(_Analysis(input_schemas), input_stats)
    lines: list[str] = []

    def notes(node: Node) -> str:
        parts = []
        bucket = getattr(node, "bucket_capacity", None)
        if bucket is not None and not isinstance(node, (Select, Project,
                                                        Limit, Scan)):
            parts.append(f"bucket={bucket}")
        if isinstance(node, GroupBy) and node.partial_capacity is not None:
            parts.append(f"partial={node.partial_capacity}")
        if isinstance(node, (Join, GroupBy)) and node.out_capacity is not None:
            parts.append(f"out={node.out_capacity}")
        stages = getattr(node, "stages", None)
        if stages is not None:
            parts.append(f"stages={stages}")
        if getattr(node, "shuffle_mode", "alltoall") != "alltoall":
            parts.append(f"mode={node.shuffle_mode}")
        if _node_cost_sized(node):
            parts.append("cost-sized")
        if est is not None:
            s = est.stats(node)
            if s is not None:
                parts.append(f"~rows={int(round(s.rows))}")
        if recovery:
            rungs = _recovery_rungs(node)
            if rungs:
                parts.append("recovery=" + "+".join(rungs))
        return (", " + ", ".join(parts)) if parts else ""

    def walk(node: Node, depth: int):
        pad = "  " * depth
        if isinstance(node, Scan):
            part = ""
            pt = node.partitioning
            if isinstance(pt, RangePartitioning):
                part = f", partitioned=range{pt.keys}/{pt.num_partitions}"
            elif pt is not None:
                part = (f", partitioned=hash{pt.keys}%"
                        f"{pt.num_partitions}@seed{pt.seed}")
            txt = f"Scan(slot={node.slot}{part}"
        elif isinstance(node, Select):
            txt = f"Select(key={node.key!r}, columns={node.columns}"
        elif isinstance(node, Project):
            txt = f"Project(columns={node.columns}"
        elif isinstance(node, Limit):
            txt = f"Limit(n={node.n}"
        elif isinstance(node, Repartition):
            txt = (f"Repartition(keys={node.keys}, seed={node.seed}, "
                   f"shuffle={_shuffle_word(node.skip_shuffle)}")
        elif isinstance(node, Join):
            extra = ""
            if node.align is not None:
                extra = f", align={node.align}{node.align_keys}"
            txt = (f"Join(on={node.on}, how={node.how}, "
                   f"algorithm={node.algorithm}, "
                   f"left={_shuffle_word(node.skip_left_shuffle)}, "
                   f"right={_shuffle_word(node.skip_right_shuffle)}{extra}")
        elif isinstance(node, GroupBy):
            txt = (f"GroupBy(keys={node.keys}, aggs={node.pairs}, "
                   f"strategy={node.strategy}, "
                   f"shuffle={_shuffle_word(node.skip_shuffle)}")
        elif isinstance(node, Sort):
            txt = (f"Sort(by={node.by}, "
                   f"shuffle={_shuffle_word(node.skip_shuffle)}")
        elif isinstance(node, Window):
            fn_names = tuple(A.window_output_name(fn, col, off)
                             for fn, col, off in node.funcs)
            txt = (f"Window(by={node.by}, order_by={node.order_by}, "
                   f"funcs={fn_names}, "
                   f"shuffle={_shuffle_word(node.skip_shuffle)}")
        elif isinstance(node, SetOp):
            extra = f", mode={node.mode}" if isinstance(node, Difference) \
                else ""
            txt = (f"{type(node).__name__}("
                   f"left={_shuffle_word(node.skip_left_shuffle)}, "
                   f"right={_shuffle_word(node.skip_right_shuffle)}{extra}")
        elif isinstance(node, Distinct):
            txt = f"Distinct(shuffle={_shuffle_word(node.skip_shuffle)}"
        else:
            txt = f"{type(node).__name__}("
        lines.append(f"{pad}{txt}{notes(node)})")
        for c in children(node):
            walk(c, depth + 1)

    walk(plan, 0)
    return "\n".join(lines)
