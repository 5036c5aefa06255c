"""DistContext — the CylonContext analogue (paper §II-C, Fig. 4).

Cylon's ``CylonContext::InitDistributed(mpi_config)`` binds the library to a
communicator; here the communicator is a **JAX mesh axis**. A
:class:`DistContext` owns ``(mesh, axis_name)`` and exposes the distributed
relational operators as jitted ``shard_map`` programs: the BSP worker code in
``ops_dist.py`` runs once per shard in SPMD lockstep, and the MPI AllToAll
becomes ``jax.lax.all_to_all`` over ``axis_name``.

Every operator — eager or lazy — executes through ONE path: build a logical
plan (``repro.core.plan``), compile it to a single ``shard_map`` body, run it
under ``jit`` keyed by the canonicalized plan. The eager methods below are
one-node plans (semantics identical to the pre-plan implementation: same
shuffles, same seeds, same stats); :meth:`frame` opens the lazy builder
whose ``collect()`` fuses a whole chain into one dispatch with the
optimizer's pushdowns and shuffle elisions applied.

A distributed table (:class:`DistTable`) is the global view: every column is
a device array whose leading dim is ``num_shards * local_capacity`` (sharded
over the shuffle axis), plus per-shard ``row_counts``. Shard *i* owns rows
``[i*C, i*C + row_counts[i])`` — Cylon's "each worker holds a partition of
the table" made explicit in the array layout. A table also carries an
optional static :class:`~repro.core.repartition.Partitioning` tag recording
how its rows are placed; ``ctx.frame`` threads the tag into the optimizer,
which elides shuffles the tag proves redundant.

Transport selection (paper §II-D: TCP vs Infiniband) becomes *mesh-axis
selection*: shuffling over an intra-pod axis rides ICI; an axis that spans
pods rides DCN. Same operator code, different wire — the paper's
communication-layer abstraction, preserved.

Host spans. Each query's host work is recorded as
``jax.profiler.TraceAnnotation`` spans, all with the query's number
(``query=<n>``, counted per context) so one query's spans can be told from
another's: ``engine.submit`` (the whole of :meth:`DistContext.submit`)
holds ``engine.plan`` (optimize or cost model, plus the cache key) and one
of ``engine.dispatch`` (the jitted call of a plan-cache hit) or
``engine.compile`` (a miss: trace, compile or persistent-cache load and the
first enqueue; ``cache=`` names the key's namespace); ``engine.retry``
(``rung=``) is each recovery rung after the first attempt, and
``engine.verify`` is a future's finalize (the overflow readback and any
late retry). The spans are recorded only while a profiler trace runs;
otherwise each costs one annotation enter and exit.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import os
import threading
import time
import weakref
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import faults as FLT
from repro.core import ops_agg as A
from repro.core import plan as PL
from repro.core import stats as ST
from repro.core.plan_cache import PlanCache
from repro.core.repartition import (Partitioning, RangePartitioning,
                                    fresh_range_fingerprint)
from repro.core.stats import TableStats
from repro.core.table import KEY_DTYPES, Table
from repro.kernels import ops as kops
from repro.utils import ceil_div, make_mesh, shard_map


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DistTable:
    """Global view of a sharded Table: columns (P*C, ...) + row_counts (P,).

    ``partitioning`` is static placement metadata (not a pytree leaf): when
    set, rows satisfy ``shard == hash(keys) % num_partitions`` — the
    invariant the plan optimizer uses to elide shuffles.

    ``stats`` is static cardinality metadata (also not a leaf): exact
    :class:`~repro.core.stats.TableStats` on a table that went through
    :meth:`DistContext.analyze`, estimator-propagated stats on operator
    outputs built from analyzed inputs, None otherwise. When present the
    plan optimizer's cost model right-sizes shuffle buckets and picks
    per-node strategies from it.
    """

    columns: dict[str, jax.Array]
    row_counts: jax.Array  # (num_shards,) int32
    partitioning: Partitioning | None = None
    stats: "TableStats | None" = None

    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        return ((tuple(self.columns[n] for n in names), self.row_counts),
                (names, self.partitioning, self.stats))

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, partitioning, stats = aux
        cols, rc = children
        return cls(dict(zip(names, cols)), rc, partitioning, stats)

    @property
    def num_shards(self) -> int:
        return self.row_counts.shape[0]

    @property
    def local_capacity(self) -> int:
        return next(iter(self.columns.values())).shape[0] // self.num_shards

    @property
    def column_names(self) -> list[str]:
        return sorted(self.columns)

    @property
    def schema(self) -> dict[str, jax.ShapeDtypeStruct]:
        """Per-row schema: name -> ShapeDtypeStruct of the trailing shape."""
        return {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
                for k, v in sorted(self.columns.items())}

    def global_rows(self) -> jax.Array:
        return jnp.sum(self.row_counts)

    def to_table(self) -> Table:
        """Collapse to a single host-side Table (valid rows compacted)."""
        p, c = self.num_shards, self.local_capacity
        counts = np.asarray(self.row_counts)
        cols = {}
        for k, v in self.columns.items():
            a = np.asarray(v).reshape((p, c) + tuple(v.shape[1:]))
            cols[k] = np.concatenate([a[i, : counts[i]] for i in range(p)], axis=0)
        n = int(counts.sum())
        return Table.from_arrays(cols, row_count=n)


class PlanFuture:
    """Handle to an asynchronously dispatched plan execution.

    ``DistContext.submit`` returns one of these IMMEDIATELY after the XLA
    dispatch — JAX's async runtime means the computation is enqueued, not
    finished, and critically no host sync has happened yet: the overflow
    counters of a cost-sized plan stay ON DEVICE until :meth:`result`.
    That is the serving unlock — a latency-critical loop used to pay one
    blocking device round-trip per cost-sized collect just to learn that
    (almost always) nothing overflowed.

    :meth:`result` performs the deferred verification: it fetches the
    overflow counters (by which point the work has typically long
    finished), and if a cost-sized capacity DID overflow it runs the
    safe-capacity retry *late* — the never-wrong-results contract is
    preserved because the table is only observable through this method.
    Verification also happens opportunistically when a LATER ``submit``
    finds this future's counters already device-ready (folded into the
    next dispatch at zero sync cost).
    """

    def __init__(self, finalize: Callable | None,
                 overflow_arrays: tuple = ()):
        self._finalize = finalize
        self._overflow = tuple(overflow_arrays)
        self._out = None
        self._error: BaseException | None = None
        self._lock = threading.Lock()  # resolve-once under concurrent result()

    @classmethod
    def failed(cls, error: BaseException) -> "PlanFuture":
        """A future already resolved exceptionally — dispatch failed
        before anything could be enqueued. ``result()`` re-raises."""
        fut = cls(None)
        fut._error = error
        return fut

    @property
    def done(self) -> bool:
        """True once resolved — to a verified result OR exceptionally."""
        return self._out is not None or self._error is not None

    def ready(self) -> bool:
        """Best-effort: is the deferred verification now sync-free (every
        overflow counter already on host-reachable memory)? False when the
        runtime cannot tell — callers must treat this as advisory."""
        if self.done:
            return True
        try:
            return all(bool(x.is_ready()) for x in self._overflow)
        except AttributeError:
            return False

    def result_with_stats(self):
        """Verified ``(DistTable, per-shuffle stats)`` — blocks on the
        overflow check (and runs the late safe retry) the first time.

        A failed finalization resolves the future exceptionally EXACTLY
        once: the error is stored under the lock, the finalize closure
        and overflow counters are dropped (no pinned device buffers, no
        half-finalized retry on a later call), and every subsequent call
        re-raises the same error."""
        with self._lock:
            if self._error is not None:
                raise self._error
            if self._out is None:
                try:
                    self._out = self._finalize()
                except BaseException as e:
                    self._error = e
                    raise
                finally:
                    # drop plan/table refs AND the overflow counters once
                    # resolved: a retained future must not pin device
                    # buffers, and a failed one must never re-finalize
                    self._finalize = None
                    self._overflow = ()
        return self._out

    def result(self) -> DistTable:
        """The verified output table (see :meth:`result_with_stats`)."""
        return self.result_with_stats()[0]


#: Recovery counters every context tracks (beyond ``overflow_retries``,
#: kept as its own attribute for backward compatibility). Surfaced in
#: ``cache_stats()`` and, as before/after deltas, in ``ServingReport``.
_RECOVERY_KEYS = ("degraded_kernel", "degraded_shuffle", "compile_retries",
                  "generic_retries", "quarantines", "failed_queries")


class DistContext:
    """Binds the relational operators to a mesh axis (the 'communicator').

    Parameters
    ----------
    mesh: the device mesh; defaults to a 1-D mesh over all local devices.
    axis_name: the mesh axis rows shuffle over (must exist in `mesh`).
    plan_cache: the canonical-plan executable cache (fresh LRU if None).
    faults: fault injection — a ``repro.core.faults.FaultRegistry``, a
        sequence of ``FaultPlan``s, or None to arm from the
        ``REPRO_FAULTS`` env spec (inert when that is unset).
    retry_policy: bounds + backoff for the recovery ladder
        (``repro.core.faults.RetryPolicy``; the default never sleeps).
    validate: post-execution result validation (row-count/received
        invariants + NaN scan at ``result()`` time). None = auto: on
        exactly when faults are armed or ``REPRO_VALIDATE`` is set, so
        the fault-free serving path pays zero extra host syncs.
    """

    def __init__(self, mesh: Mesh | None = None, axis_name: str = "shuffle",
                 plan_cache: PlanCache | None = None,
                 faults: "FLT.FaultRegistry | Sequence[FLT.FaultPlan] | None"
                 = None,
                 retry_policy: FLT.RetryPolicy | None = None,
                 validate: bool | None = None):
        if mesh is None:
            mesh = make_mesh((jax.device_count(),), (axis_name,))
        assert axis_name in mesh.axis_names, (axis_name, mesh.axis_names)
        self.mesh = mesh
        self.axis_name = axis_name
        # canonical-plan -> compiled-executable cache, shared by every
        # client submitting through this context (eager ops, collect,
        # collect_async/submit alike). LRU with budgets + hit/miss/evict/
        # recompile counters — see repro.core.plan_cache.
        self.plan_cache = plan_cache if plan_cache is not None else PlanCache()
        if faults is None:
            faults = FLT.from_env()
        elif not isinstance(faults, FLT.FaultRegistry):
            faults = FLT.FaultRegistry(tuple(faults))
        # the armed fault registry (empty = inert) — every dispatch and
        # finalization runs under its thread-local scope
        self.faults = faults if faults is not None else FLT.FaultRegistry()
        self.retry_policy = retry_policy if retry_policy is not None \
            else FLT.RetryPolicy()
        self._validate = validate
        # recovery-ladder counters (see _RECOVERY_KEYS / cache_stats)
        self.recovery = {k: 0 for k in _RECOVERY_KEYS}
        # how many cost-sized plans overflowed their estimated capacities
        # and were re-run at conservative sizes (the overflow-retry path)
        self.overflow_retries = 0
        # canonical keys of cost-sized plans whose estimates already
        # proved wrong: later collects go STRAIGHT to the safe plan (one
        # conservative execution, not a doomed sized run + retry each time)
        self._overflow_bad: set = set()
        # in-flight futures with deferred overflow verification; weakly
        # held so an abandoned future never pins its tables
        self._pending: list = []
        # numbers each submit; every host span of a query carries it
        self._queries = itertools.count(1)
        # host seconds spent in dispatches that traced and compiled (or
        # loaded from the persistent cache): the engine.compile spans
        self.compile_s = 0.0
        # guards _pending / _overflow_bad / overflow_retries: submit and
        # result() may be called from multiple client threads. Reentrant
        # because a finalize running under it may fold further bookkeeping.
        self._lock = threading.RLock()

    # -- properties ---------------------------------------------------------
    @property
    def num_shards(self) -> int:
        return self.mesh.shape[self.axis_name]

    def _sharding(self, ndim: int) -> NamedSharding:
        spec = P(self.axis_name, *([None] * (ndim - 1)))
        return NamedSharding(self.mesh, spec)

    # -- table placement ----------------------------------------------------
    def scatter(self, table: Table, *, local_capacity: int | None = None
                ) -> DistTable:
        """Round-robin-block scatter a host Table into `num_shards` shards."""
        p = self.num_shards
        if p == 1 and (local_capacity is None
                       or local_capacity == table.capacity):
            # single-shard fast path: the table IS the only partition —
            # no host round-trip / repack (the ETL hot loop rides this)
            cols = {k: jax.device_put(v, self._sharding(v.ndim))
                    for k, v in table.columns.items()}
            rc = jax.device_put(
                jnp.reshape(jnp.asarray(table.row_count, jnp.int32), (1,)),
                NamedSharding(self.mesh, P(self.axis_name)))
            return DistTable(cols, rc)
        n = int(table.row_count)
        c = local_capacity or max(1, ceil_div(table.capacity, p))
        counts = np.full((p,), n // p, np.int32)
        counts[: n % p] += 1
        assert counts.max() <= c, (counts.max(), c)
        offs = np.concatenate([[0], np.cumsum(counts)])
        cols = {}
        for k in table.column_names:
            v = np.asarray(table.columns[k])
            out = np.zeros((p, c) + v.shape[1:], v.dtype)
            for i in range(p):
                out[i, : counts[i]] = v[offs[i] : offs[i + 1]]
            cols[k] = jax.device_put(
                out.reshape((p * c,) + v.shape[1:]), self._sharding(v.ndim))
        rc = jax.device_put(jnp.asarray(counts),
                            NamedSharding(self.mesh, P(self.axis_name)))
        return DistTable(cols, rc)

    def from_local_parts(self, parts: Sequence[Table]) -> DistTable:
        """Build a DistTable from one local Table per shard (equal capacity)."""
        p = self.num_shards
        assert len(parts) == p, (len(parts), p)
        caps = {t.capacity for t in parts}
        assert len(caps) == 1, caps
        cols = {}
        for k in parts[0].column_names:
            v = np.concatenate([np.asarray(t.columns[k]) for t in parts], axis=0)
            cols[k] = jax.device_put(v, self._sharding(v.ndim))
        rc = jnp.asarray([int(t.row_count) for t in parts], jnp.int32)
        rc = jax.device_put(rc, NamedSharding(self.mesh, P(self.axis_name)))
        return DistTable(cols, rc)

    # -- statistics (the cost-model input) -----------------------------------
    def analyze(self, t: DistTable) -> DistTable:
        """Compute exact :class:`~repro.core.stats.TableStats` for ``t``
        in one cheap vectorized pass and cache them on the table.

        Stats cover the global row count, exact per-shard max, and per
        key column min/max plus an NDV sketch (hash-bitmap linear
        counting — the murmur3 kernel already on the shuffle path). Every
        plan built over the returned table is cost-sized: shuffle buckets
        shrink to estimated occupancy, GroupBy picks ``shuffle`` vs
        ``two_phase`` per node, joins budget their outputs by estimated
        match count. Idempotent: a table that already carries stats is
        returned as-is.
        """
        if t.stats is not None:
            return t
        c = t.local_capacity
        counts = np.asarray(t.row_counts)
        rows = int(counts.sum())
        names = tuple(k for k, v in sorted(t.columns.items())
                      if v.ndim == 1 and v.dtype in KEY_DTYPES)

        def sweep(cols, rc):
            # per shard: a Pallas kernel (the hash) cannot be partitioned
            # automatically, so the sweep runs inside shard_map
            valid = jnp.arange(c) < rc[0]
            return ST.sketch_columns(cols, valid, names,
                                     axis_name=self.axis_name)

        axis = P(self.axis_name)
        sk = jax.jit(shard_map(sweep, mesh=self.mesh, in_specs=(axis, axis),
                               out_specs=P()))(
            {n: t.columns[n] for n in names}, t.row_counts)
        cols = []
        for n in names:
            filled, lo, hi = sk[n]
            cols.append((n, ST.ColumnStats(
                ST.linear_count(int(filled), rows),
                float(np.asarray(lo)), float(np.asarray(hi)))))
        stats = TableStats(rows=float(rows), columns=tuple(cols),
                           max_shard_rows=float(counts.max(initial=0)))
        return dataclasses.replace(t, stats=stats)

    # -- the lazy builder ----------------------------------------------------
    def frame(self, table: Table | DistTable):
        """Open a :class:`~repro.core.frame.LazyFrame` over ``table``.

        Operators chained on the frame defer until ``collect()``, which
        optimizes the whole plan (predicate/projection pushdown, shuffle
        elision from the table's Partitioning tag) and runs it as ONE
        shard_map program.
        """
        from repro.core.frame import LazyFrame

        return LazyFrame.scan(self, table)

    # -- shard_map plumbing ---------------------------------------------------
    def _make_global(self, body: Callable) -> Callable:
        """Wrap a per-shard `body(*tables) -> (Table, stats)` in shard_map."""
        axis = self.axis_name

        def local_fn(*local_tabs):
            tables = [Table(cols, rc.reshape(())) for cols, rc in local_tabs]
            out, stats = body(*tables)
            stats = jax.tree.map(lambda x: jnp.asarray(x)[None], stats)
            return out.columns, out.row_count[None], stats

        def global_fn(*args):
            # P(axis) as a pytree-prefix spec: every leaf is per-shard data
            # sharded on its leading dim (columns, row counts, stats alike).
            fn = shard_map(local_fn, mesh=self.mesh, in_specs=P(axis),
                           out_specs=P(axis))
            return fn(*args)

        return global_fn

    def cache_stats(self) -> dict:
        """Plan-cache counter snapshot (hits/misses/evictions/recompiles
        plus residency) — the serving benchmark's warm-path gate reads
        this before and after a run to assert 0 recompiles. Also carries
        the plan verifier's ``verify_runs``/``verify_findings`` counters
        (process-wide; see ``repro.core.verify``), this context's
        recovery-ladder counters (``overflow_retries``,
        ``degraded_kernel``/``degraded_shuffle``, ``compile_retries``,
        ``generic_retries``, ``quarantines``, ``failed_queries``), the
        fault registry's ``fault_calls``/``fault_fires``, and ``compile_s``:
        host seconds spent in dispatches that compiled (plan-cache misses
        and uncacheable plans)."""
        from repro.core import verify as V

        with self._lock:
            rec = dict(self.recovery)
            rec["overflow_retries"] = self.overflow_retries
            rec["compile_s"] = self.compile_s
        return {**self.plan_cache.stats(), **V.counter_snapshot(),
                **self.faults.stats(), **rec}

    def _bump(self, counter: str, n: int = 1):
        with self._lock:
            self.recovery[counter] += n

    # -- result validation (the quarantine gate) ------------------------------
    def _validation_on(self) -> bool:
        """Finalize-time result validation costs host syncs (row counts,
        a NaN scan), so it is opt-in: explicit ``validate=``, the
        ``REPRO_VALIDATE`` env, or automatically whenever faults are
        armed (a chaos run must detect its own poison)."""
        if self._validate is not None:
            return bool(self._validate)
        return self.faults.active or \
            os.environ.get("REPRO_VALIDATE", "") not in ("", "0")

    def _validate_result(self, out: DistTable, stats,
                         tabs: Sequence[DistTable]) -> list[str]:
        """Post-execution invariants; non-empty findings quarantine the
        run (one fully-degraded re-execution). Checks: per-shard row
        counts within [0, capacity]; every shuffle's received-row total
        bounded by the rows the inputs could possibly hold (garbled
        counts decode to absurd totals); no NaN in any valid float cell
        (kernel/chunk poison). Assumes NaN-free user data — documented
        with the validation knob."""
        problems = []
        p, c = out.num_shards, out.local_capacity
        rc = np.asarray(out.row_counts)
        if (rc < 0).any() or (rc > c).any():
            problems.append(f"row_counts outside [0, {c}]: {rc.tolist()}")
        cap_total = sum(t.num_shards * t.local_capacity for t in tabs)
        for i, s in enumerate(stats):
            recv = int(np.asarray(s.received).sum())
            if recv < 0 or recv > cap_total:
                problems.append(f"shuffle {i} received {recv} rows; "
                                f"inputs hold at most {cap_total}")
        idx = np.arange(p * c)
        valid = (idx % c) < np.clip(rc, 0, c)[idx // c]
        for name, col in sorted(out.columns.items()):
            if not jnp.issubdtype(col.dtype, jnp.floating):
                continue
            # float32 staging keeps the scan clear of ml_dtypes (bf16)
            # ufunc gaps; any float NaN survives the cast
            a = np.asarray(col).astype(np.float32)
            mask = valid.reshape((-1,) + (1,) * (a.ndim - 1))
            if np.isnan(np.where(mask, a, 0.0)).any():
                problems.append(f"NaN in column {name!r}")
        return problems

    def _run(self, key, body: Callable, tabs: Sequence[DistTable], *,
             query: int = 0):
        """Execute per-shard `body` over DistTables under shard_map + jit.

        ``key`` controls the executable cache: None -> never cached (a
        plan neither canonical- nor content-keyable re-traces per call —
        always correct). The key's own tuples strongly pin any objects
        whose equality the lookup relies on. After the lookup, the call
        runs in an ``engine.dispatch`` span on a hit and in an
        ``engine.compile`` span otherwise, whose seconds add to
        :attr:`compile_s`.
        """
        global_fn = self._make_global(body)
        args = tuple((t.columns, t.row_counts) for t in tabs)
        sig = jitted = None
        if key is not None:
            sig = (key, tuple(
                tuple(sorted((k, v.shape, str(v.dtype))
                             for k, v in t.columns.items()))
                for t in tabs))
            jitted = self.plan_cache.get(sig)
        cached = jitted is not None
        if cached and FLT.check("compile") is not None:
            # injected: the cached executable is corrupt. Drop the entry
            # here so the ladder's plain retry compiles fresh.
            self.plan_cache.invalidate(sig)
            raise FLT.FaultError("compile", "cached executable corrupt")
        if jitted is None:
            jitted = jax.jit(global_fn)
        reg = FLT.current()
        fires = reg.fire_count() if reg is not None else 0
        t0 = time.perf_counter()
        with TraceAnnotation("engine.dispatch" if cached else "engine.compile",
                             query=query,
                             cache=key[0] if key is not None else "none"):
            cols, rc, stats = jitted(*args)  # first call on a miss = the trace
        if not cached:
            with self._lock:
                self.compile_s += time.perf_counter() - t0
        poisoned = reg is not None and reg.fire_count() != fires
        if sig is not None and not cached and not poisoned:
            # admit only AFTER a successful fault-free first call: a trace
            # that raised (put never reached) or absorbed an injected
            # fault (poisoned constants baked in) must never leave a
            # broken executable behind for later cache hits
            self.plan_cache.put(sig, jitted)
        return DistTable(cols, rc), stats

    def submit(self, plan: PL.Node, tabs: Sequence[DistTable], *,
               optimize: bool = False, report: list | None = None
               ) -> PlanFuture:
        """Async dispatch: compile (or cache-hit) + enqueue the plan and
        return a :class:`PlanFuture` IMMEDIATELY — the concurrent-query
        serving path. The single execution pipeline is unchanged:
        (optionally optimized) plan -> one shard_map body -> jit keyed by
        the canonical plan in :attr:`plan_cache`; plans containing keyless
        user lambdas fall back to content keys (``PL.identity_key`` — the
        code object plus the values of its captures/defaults/referenced
        globals), so ad-hoc predicates stop re-jitting per call while a
        rebound global or changed capture still misses. Predicates that
        cannot be safely content-keyed are simply never cached.

        ``report``, when given, receives one static record per potential
        shuffle at TRACE time — a jit-cache hit leaves it empty (use
        ``LazyFrame.plan_report()`` for an always-filled dry run).

        When any input carries TableStats the cost model sizes the plan's
        capacities from cardinality ESTIMATES. Estimates can be wrong, so
        the future is the overflow-safe point: verification of the
        overflow counters is DEFERRED — no host sync happens here — until
        ``future.result()``, or until a later ``submit`` finds the
        counters already device-ready (the check folds into the next
        dispatch). If a cost-sized capacity did overflow (per-entry
        attribution via ``plan.cost_sized_stats_mask`` — overflow on a
        user-set capacity keeps the pre-existing surface-in-stats contract
        and never triggers a retry), the verification runs the safe-
        capacity recompile (``execute_plan(..., safe_capacity=True)``,
        cached under its own ``plan-safe`` key) and the future resolves to
        the retried result — never wrong results, because the table is
        only observable through ``result()``. ``self.overflow_retries``
        counts these; a plan key that failed once goes straight to the
        safe plan on later submits, and outputs of a failed-estimate run
        carry NO propagated stats, so downstream stages fall back to
        conservative sizing instead of cascading the bad numbers.

        That overflow retry is one rung of a general recovery LADDER
        (``repro.core.faults``): every execution attempt runs under
        :attr:`retry_policy` (bounded attempts, deterministic backoff)
        and a classified failure degrades the next attempt — Pallas
        kernel fault -> XLA oracle; staged/ring shuffle fault ->
        monolithic AllToAll; corrupt cached executable -> fresh compile;
        a result that fails validation (NaN / invariant violation, when
        validation is on) is quarantined and re-executed once fully
        degraded. Degraded executables cache under a ``plan-degraded``
        namespace so they never collide with the primary ones. A failure
        that exhausts the ladder resolves the future EXCEPTIONALLY — a
        dispatch-time error returns an already-failed future rather than
        raising, so one bad query can never kill a serving loop or
        poison the pending-fold list; ``result()`` re-raises for its
        owner alone.
        """
        query = next(self._queries)
        with TraceAnnotation("engine.submit", query=query):
            try:
                with FLT.scope(self.faults):
                    return self._submit_impl(plan, tabs, optimize=optimize,
                                             report=report, query=query)
            except Exception as e:
                self._bump("failed_queries")
                return PlanFuture.failed(e)

    def _submit_impl(self, plan: PL.Node, tabs: Sequence[DistTable], *,
                     optimize: bool, report: list | None,
                     query: int) -> PlanFuture:
        p = self.num_shards
        logical = plan
        schemas = [t.schema for t in tabs]
        input_stats = [t.stats for t in tabs]
        have_stats = any(s is not None for s in input_stats)
        policy = self.retry_policy
        with TraceAnnotation("engine.plan", query=query):
            if optimize:
                plan, part = PL.optimize_with_partitioning(
                    plan, schemas, p, input_stats=input_stats)
            else:
                # eager one-node plans skip the logical rewrites but still get
                # strategy resolution + capacity sizing from the cost model
                part = PL.output_partitioning(plan, schemas, p)
                plan = PL.apply_cost_model(plan, schemas, p, input_stats)
            if isinstance(part, RangePartitioning):
                # materialized tables get a unique provenance token: two
                # executions of the same plan shape over different inputs have
                # different splitters and must never fingerprint-match
                part = dataclasses.replace(
                    part, fingerprint=fresh_range_fingerprint())
            key = PL.canonical_key(plan)
            if key is None:
                # content-based fallback for keyless user lambdas; None when
                # the plan cannot be safely keyed (opaque callable, unhashable
                # capture) — _run then skips the cache entirely
                ikey = PL.identity_key(plan)
                run_key = ("plan-id", ikey) if ikey is not None else None
            else:
                run_key = ("plan", key)
        sized = have_stats and PL.plan_cost_sized(plan)
        safe_memo: dict = {}  # the safe plan is derived at most once

        def run_variant(safe: bool, degrade: frozenset):
            """Execute one ladder rung: the primary or safe-capacity
            plan, further degraded per ``degrade``. Undegraded runs keep
            the pre-existing ``plan``/``plan-safe`` cache namespaces;
            degraded executables get their own ``plan-degraded`` keys."""
            if safe:
                if "plan" not in safe_memo:
                    if optimize:
                        sp, _ = PL.optimize_with_partitioning(
                            logical, schemas, p)
                    else:
                        sp = PL.apply_cost_model(logical, schemas, p, None)
                    safe_memo["plan"] = sp
                v_plan, ns = safe_memo["plan"], "plan-safe"
            else:
                v_plan, ns = plan, "plan"
            if FLT.MONO_SHUFFLE in degrade:
                v_plan = PL.degrade_shuffles(v_plan)
            if not safe and v_plan is plan:
                base = run_key  # keyed once, under engine.plan
            else:
                v_key = PL.canonical_key(v_plan)
                if v_key is not None:
                    base = (ns, v_key)
                else:
                    ik = PL.identity_key(v_plan)
                    base = (ns + "-id", ik) if ik is not None else None
            if base is None:
                v_run_key = None
            elif degrade:
                v_run_key = ("plan-degraded", tuple(sorted(degrade))) + base
            else:
                v_run_key = base

            def body(*tables):
                return PL.execute_plan(
                    v_plan, tables, axis_name=self.axis_name, num_shards=p,
                    report=report if not (safe or degrade) else None,
                    safe_capacity=safe)

            if FLT.ORACLE_KERNEL in degrade:
                with kops.oracle_scope():
                    return self._run(v_run_key, body, tabs, query=query)
            return self._run(v_run_key, body, tabs, query=query)

        def run_with_recovery(safe: bool, degrade: frozenset = frozenset()):
            """Walk the ladder: execute, classify the failure, degrade
            the next attempt — bounded by the retry policy. Only injected
            ``FaultError``s ride the ladder; genuine programming errors
            propagate immediately (retrying them is noise). Each attempt
            after the first runs in an ``engine.retry`` span named by its
            rung."""
            degrade = set(degrade)
            last = rung = None
            for attempt in range(1, max(1, policy.max_attempts) + 1):
                span = contextlib.nullcontext() if attempt == 1 else \
                    TraceAnnotation("engine.retry", query=query, rung=rung)
                try:
                    with span:
                        if attempt > 1:
                            policy.sleep(attempt - 1)
                        out, stats = run_variant(safe, frozenset(degrade))
                    return out, stats, frozenset(degrade)
                except FLT.FaultError as e:
                    last = e
                    rung = FLT.rung_for(e)
                    if rung == FLT.ORACLE_KERNEL:
                        degrade.add(FLT.ORACLE_KERNEL)
                        self._bump("degraded_kernel")
                    elif rung == FLT.MONO_SHUFFLE:
                        degrade.add(FLT.MONO_SHUFFLE)
                        self._bump("degraded_shuffle")
                    elif rung == "recompile":
                        # _run already invalidated the corrupt entry; the
                        # plain retry recompiles fresh
                        self._bump("compile_retries")
                    else:
                        self._bump("generic_retries")
            raise RuntimeError(
                f"plan failed after {policy.max_attempts} attempts "
                f"(degradations tried: {sorted(degrade)})") from last

        with self._lock:
            bad_estimates = sized and run_key is not None \
                and run_key in self._overflow_bad
        # this plan's estimates already failed once -> straight to safe
        out, stats, degraded = run_with_recovery(safe=bad_estimates)

        def finalize_inner():
            nonlocal out, stats, bad_estimates, degraded
            if sized and not bad_estimates:
                mask = PL.cost_sized_stats_mask(plan)
                if len(mask) != len(stats):  # defensive: never mis-attribute
                    mask = [True] * len(stats)
                overflow = sum(int(np.asarray(s.overflow).sum())
                               for s, m in zip(stats, mask) if m)
                if overflow > 0:  # late safe-capacity retry
                    bad_estimates = True
                    with self._lock:
                        self.overflow_retries += 1
                        if run_key is not None:
                            self._overflow_bad.add(run_key)
                    with TraceAnnotation("engine.retry", query=query,
                                         rung="safe-capacity"):
                        out, stats, degraded = run_with_recovery(
                            safe=True, degrade=degraded)
            if self._validation_on():
                problems = self._validate_result(out, stats, tabs)
                if problems:
                    # quarantine: drop the suspect result, re-execute once
                    # fully degraded (oracle kernels + monolithic
                    # shuffles — every rung that changes the program)
                    self._bump("quarantines")
                    with TraceAnnotation("engine.retry", query=query,
                                         rung="quarantine"):
                        out, stats, degraded = run_with_recovery(
                            safe=bad_estimates,
                            degrade=frozenset((FLT.ORACLE_KERNEL,
                                               FLT.MONO_SHUFFLE)))
                    problems = self._validate_result(out, stats, tabs)
                    if problems:
                        raise RuntimeError(
                            "result failed validation after degraded "
                            "re-execution: " + "; ".join(problems))
            est = None
            if have_stats and not bad_estimates:
                est = PL.estimate_output_stats(plan, schemas, input_stats)
            final = dataclasses.replace(out, partitioning=part, stats=est)
            return final, stats

        def finalize():
            try:
                with TraceAnnotation("engine.verify", query=query), \
                        FLT.scope(self.faults):
                    return finalize_inner()
            except Exception:
                self._bump("failed_queries")
                raise

        # only a cost-sized first pass has anything to verify: everything
        # else resolves without ever touching the host
        overflow_arrays = tuple(s.overflow for s in stats) \
            if sized and not bad_estimates else ()
        fut = PlanFuture(finalize, overflow_arrays)
        self._fold_pending(skip=fut)
        if overflow_arrays or self._validation_on():
            with self._lock:
                self._pending.append(weakref.ref(fut))
        return fut

    def _fold_pending(self, skip: PlanFuture | None = None):
        """Verify earlier futures whose overflow counters are already
        device-ready — the deferred check folded into this dispatch at
        zero sync cost. Dropped or resolved futures fall out of the list;
        a future whose counters are still in flight stays deferred.
        The pending list is swapped out under the lock and resolved
        outside it (resolution may itself dispatch a safe retry)."""
        with self._lock:
            pending, self._pending = self._pending, []
        still = []
        for ref in pending:
            f = ref()
            if f is None or f.done or f is skip:
                continue
            if f.ready():
                try:
                    f.result_with_stats()
                except Exception:
                    # the error is stored on the future for its OWNER to
                    # re-raise from result(); a background fold must not
                    # let one bad query abort an unrelated dispatch
                    pass
            else:
                still.append(ref)
        with self._lock:
            self._pending.extend(still)

    def drain(self, raise_errors: bool = True):
        """Block until every outstanding future is verified (the explicit
        end-of-batch sync for fire-and-forget submitters). Every future is
        resolved even when some fail; the collected errors are returned,
        and the first is re-raised unless ``raise_errors=False``."""
        with self._lock:
            pending, self._pending = self._pending, []
        errors = []
        for ref in pending:
            f = ref()
            if f is not None:
                try:
                    f.result_with_stats()
                except Exception as e:
                    errors.append(e)
        if errors and raise_errors:
            raise errors[0]
        return errors

    def _run_plan(self, plan: PL.Node, tabs: Sequence[DistTable], *,
                  optimize: bool = False, report: list | None = None):
        """Synchronous execution: :meth:`submit` + immediate verification.
        Every eager operator and ``LazyFrame.collect`` rides this; the
        semantics (overflow-safe retry, stats propagation, partitioning
        tags) live in :meth:`submit`'s future."""
        return self.submit(plan, tabs, optimize=optimize,
                           report=report).result_with_stats()

    # -- pleasingly parallel operators (no network; paper §II-B-1/2) ----------
    def select(self, t: DistTable, predicate: Callable[[dict], jax.Array],
               *, key=None, report: list | None = None) -> DistTable:
        """Filter rows by `predicate`. ``key``: optional hashable cache key
        for the predicate — without it every call recompiles (a fresh
        lambda can't be canonicalized). The key must cover any values the
        predicate CAPTURES (e.g. ``key=("q>", threshold)``); differing
        predicate code under the same key is caught by a bytecode
        fingerprint, captured values are not."""
        plan = PL.Select(PL.Scan(0), predicate, key=key)
        out, _ = self._run_plan(plan, [t], report=report)
        return out

    def project(self, t: DistTable, columns: Sequence[str],
                *, report: list | None = None) -> DistTable:
        plan = PL.Project(PL.Scan(0), tuple(columns))
        out, _ = self._run_plan(plan, [t], report=report)
        return out

    # -- shuffle-based operators (paper §II-B-3..6, Fig. 3) -------------------
    def partition_by(self, t: DistTable, keys, *, seed: int = 7,
                     bucket_capacity=None, stages: int | None = None,
                     shuffle_mode: str = "alltoall",
                     report: list | None = None):
        """Explicitly hash-repartition ``t`` on ``keys`` and tag the result.

        Pre-partition a dimension table once; every later join/groupby on
        ``keys`` (same seed) through :meth:`frame` elides its shuffle.
        ``stages``/``shuffle_mode`` tune the shuffle pipeline (bit-
        identical results for every setting; None = cost-model pick).
        """
        keys_t = (keys,) if isinstance(keys, str) else tuple(keys)
        plan = PL.Repartition(PL.Scan(0), keys_t, seed=seed,
                              bucket_capacity=bucket_capacity,
                              stages=stages, shuffle_mode=shuffle_mode)
        return self._run_plan(plan, [t], report=report)

    def join(self, left: DistTable, right: DistTable, on, *, how="inner",
             algorithm="sort", bucket_capacity=None, out_capacity=None,
             seed: int = 7, stages: int | None = None,
             shuffle_mode: str = "alltoall", report: list | None = None):
        on_t = (on,) if isinstance(on, str) else tuple(on)
        plan = PL.Join(PL.Scan(0), PL.Scan(1), on_t, how=how,
                       algorithm=algorithm, bucket_capacity=bucket_capacity,
                       out_capacity=out_capacity, seed=seed,
                       stages=stages, shuffle_mode=shuffle_mode)
        return self._run_plan(plan, [left, right], report=report)

    def union(self, a: DistTable, b: DistTable, *, bucket_capacity=None,
              seed: int = 7, stages: int | None = None,
              shuffle_mode: str = "alltoall", report: list | None = None):
        plan = PL.Union(PL.Scan(0), PL.Scan(1),
                        bucket_capacity=bucket_capacity, seed=seed,
                        stages=stages, shuffle_mode=shuffle_mode)
        return self._run_plan(plan, [a, b], report=report)

    def intersect(self, a: DistTable, b: DistTable, *, bucket_capacity=None,
                  seed: int = 7, stages: int | None = None,
                  shuffle_mode: str = "alltoall", report: list | None = None):
        plan = PL.Intersect(PL.Scan(0), PL.Scan(1),
                            bucket_capacity=bucket_capacity, seed=seed,
                            stages=stages, shuffle_mode=shuffle_mode)
        return self._run_plan(plan, [a, b], report=report)

    def difference(self, a: DistTable, b: DistTable, *, mode="symmetric",
                   bucket_capacity=None, seed: int = 7,
                   stages: int | None = None,
                   shuffle_mode: str = "alltoall",
                   report: list | None = None):
        plan = PL.Difference(PL.Scan(0), PL.Scan(1),
                             bucket_capacity=bucket_capacity, seed=seed,
                             mode=mode, stages=stages,
                             shuffle_mode=shuffle_mode)
        return self._run_plan(plan, [a, b], report=report)

    def distinct(self, a: DistTable, *, bucket_capacity=None, seed: int = 7,
                 stages: int | None = None, shuffle_mode: str = "alltoall",
                 report: list | None = None):
        plan = PL.Distinct(PL.Scan(0), bucket_capacity=bucket_capacity,
                           seed=seed, stages=stages,
                           shuffle_mode=shuffle_mode)
        return self._run_plan(plan, [a], report=report)

    def groupby(self, t: DistTable, keys, aggs, *, strategy: str = "auto",
                bucket_capacity=None, partial_capacity: int | None = None,
                out_capacity: int | None = None, seed: int = 7,
                stages: int | None = None, shuffle_mode: str = "alltoall",
                report: list | None = None):
        """Distributed GroupBy (strategy='auto' | 'two_phase' | 'shuffle').

        'two_phase' (arXiv:2010.14596): per-shard partial aggregates
        shuffle instead of raw rows — on low-cardinality keys this moves
        ~cardinality rows per shard instead of every raw row. 'shuffle'
        repartitions raw rows first. 'auto' (default) lets the cost model
        pick per node from the key-NDV-vs-rows crossover when ``t``
        carries stats (:meth:`analyze`), falling back to 'two_phase'
        otherwise; with stats the AllToAll ``bucket_capacity`` is also
        right-sized automatically instead of needing hand tuning.
        """
        keys_t = (keys,) if isinstance(keys, str) else tuple(keys)
        pairs = A.normalize_aggs(aggs)  # canonical form: the jit-cache key
        plan = PL.GroupBy(PL.Scan(0), keys_t, pairs, strategy=strategy,
                          bucket_capacity=bucket_capacity,
                          partial_capacity=partial_capacity,
                          out_capacity=out_capacity, seed=seed,
                          stages=stages, shuffle_mode=shuffle_mode)
        return self._run_plan(plan, [t], report=report)

    def sort(self, a: DistTable, by, *, bucket_capacity=None,
             samples_per_shard: int = 64, stages: int | None = None,
             shuffle_mode: str = "alltoall", report: list | None = None):
        """Global sort by one or more key columns (lexicographic order).

        The result carries a :class:`RangePartitioning` tag (splitter
        provenance): feeding it back through :meth:`frame` lets the
        optimizer elide the shuffle of a downstream sort/groupby/join on a
        key prefix — the sort-merge fast path.
        """
        by_t = (by,) if isinstance(by, str) else tuple(by)
        plan = PL.Sort(PL.Scan(0), by_t, bucket_capacity=bucket_capacity,
                       samples_per_shard=samples_per_shard,
                       stages=stages, shuffle_mode=shuffle_mode)
        return self._run_plan(plan, [a], report=report)

    def window(self, t: DistTable, by, funcs, *, order_by=(),
               bucket_capacity=None, samples_per_shard: int = 64,
               stages: int | None = None, shuffle_mode: str = "alltoall",
               report: list | None = None):
        """Distributed window functions (rank/lag/running aggregates).

        Range-partitions on (by + order_by) like :meth:`sort`, then
        computes every function with per-shard segment scans plus a
        boundary-carry ``all_gather`` (p scalars per carried partial —
        no AllToAll) for groups spanning shards. A table already range-
        partitioned on a matching key prefix (a :meth:`sort` output fed
        back through the one-node plan) skips the shuffle entirely. The
        result carries a :class:`RangePartitioning` tag on (by +
        order_by), so downstream sorts/groupbys/joins elide shuffles off
        it just like a sort output.
        """
        by_t = (by,) if isinstance(by, str) else tuple(by)
        order_t = (order_by,) if isinstance(order_by, str) \
            else tuple(order_by)
        pairs = A.normalize_funcs(funcs)
        plan = PL.Window(PL.Scan(0), by_t, order_t, pairs,
                         bucket_capacity=bucket_capacity,
                         samples_per_shard=samples_per_shard,
                         stages=stages, shuffle_mode=shuffle_mode)
        return self._run_plan(plan, [t], report=report)

    def limit(self, t: DistTable, n: int, *, report: list | None = None
              ) -> DistTable:
        """True global head-n (counts prefix-scan -> per-shard quota).

        Returns exactly the first ``min(n, total)`` rows in shard order —
        after :meth:`sort`, the global top-n. Rides the same one-node-plan
        path as every other eager operator.
        """
        plan = PL.Limit(PL.Scan(0), int(n))
        out, _ = self._run_plan(plan, [t], report=report)
        return out
