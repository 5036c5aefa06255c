"""Public jit'd wrappers around the Pallas kernels (with composition helpers).

The core library calls these — never the kernels directly — so the
kernel/fallback choice, padding and multi-column combination live in one
place. Off-TPU everything runs with interpret=True (bit-exact semantics).
"""
from __future__ import annotations

import functools
import threading
from contextlib import contextmanager

import jax
import jax.numpy as jnp

from repro.core import faults as FLT
from repro.kernels import ref
from repro.kernels.bitonic import DEFAULT_TILE, bitonic_sort_tiles
from repro.kernels.hash64 import hash32
from repro.kernels.histogram import bucket_histogram
from repro.kernels.segment_reduce import MAX_SEGMENTS, segment_reduce_tiles
from repro.kernels.segment_scan import segment_scan_tiles
from repro.utils import interpret_mode, next_pow2

__all__ = [
    "hash32",
    "hash_columns",
    "bucket_histogram",
    "sort_pairs",
    "segment_reduce",
    "segment_scan",
    "key_max",
    "oracle_scope",
    "oracle_only",
]


# -- the kernel -> XLA-oracle degradation rung -------------------------------
# DistContext's recovery ladder re-executes a failed plan with every Pallas
# segment kernel swapped for its bit-identical XLA oracle. The flag is
# thread-local and consulted at TRACE time (resolution below happens
# outside the inner jits, so it always takes effect — a cached trace of
# the kernel path cannot shadow it).

_oracle = threading.local()


def oracle_only() -> bool:
    """True while the calling thread is inside :func:`oracle_scope`."""
    return getattr(_oracle, "depth", 0) > 0


@contextmanager
def oracle_scope():
    """Force every segment kernel to its XLA oracle on this thread — the
    ``oracle-kernel`` recovery rung (bit-identical on the integer-valued
    inputs the engine produces)."""
    _oracle.depth = getattr(_oracle, "depth", 0) + 1
    try:
        yield
    finally:
        _oracle.depth -= 1


def _kernel_fault(out: jax.Array) -> jax.Array:
    """Apply an armed ``kernel.dispatch`` fault: raise, or return ``out``
    NaN-poisoned (floats only — result validation detects the NaNs and
    quarantines the run). No-op when no fault fires."""
    fp = FLT.check("kernel.dispatch")
    if fp is None:
        return out
    mode = fp.effective_mode
    if mode == "nan" and jnp.issubdtype(out.dtype, jnp.floating):
        return jnp.full_like(out, jnp.nan)
    raise FLT.FaultError("kernel.dispatch", f"mode={mode}")


def hash_columns(columns: list[jax.Array], seed: int = 0) -> jax.Array:
    """Row-wise uint32 hash over one or more columns (order-sensitive).

    This is the paper's multi-column record hash used by hash-partition,
    hash-join, union/intersect/difference (which hash the whole row).
    """
    assert columns, "hash_columns needs at least one column"
    h = hash32(columns[0], seed=seed)
    for c in columns[1:]:
        h = ref.hash_combine_ref(h, hash32(c, seed=seed))
    return h


def key_max(dtype) -> jax.Array:
    """Sentinel that sorts after every real key of `dtype`."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)


def segment_reduce(
    values: jax.Array,
    seg_ids: jax.Array,
    num_segments: int,
    op: str = "sum",
    *,
    use_kernel: bool | None = None,
) -> jax.Array:
    """Segmented sum/min/max: out[g] = op(values[i] where seg_ids[i] == g).

    values: (n, ...) — reductions run along the leading axis; seg_ids: (n,)
    int32, entries outside [0, num_segments) (padding uses -1) are ignored.
    Empty segments hold the op identity (ref.seg_init).

    The Pallas one-hot kernel handles 1-D f32/i32 values at ANY segment
    count — counts past MAX_SEGMENTS tile the segment axis in a second
    grid dimension (kernels/segment_reduce.py). N-D payloads fall back to
    XLA scatter-reduce; ``use_kernel=False`` forces that path (the
    bit-identical oracle the tests sweep against).

    Auto (``use_kernel=None``) takes the kernel only for single-tile
    segment counts (<= MAX_SEGMENTS), on every backend: each further
    segment tile re-reads every row, so the kernel's cost grows as
    rows x segments, and a large groupby goes to the XLA scatter.

    Resolution happens HERE, outside the jit: :func:`oracle_scope` (the
    recovery ladder) overrides any choice to the XLA path, and an armed
    ``kernel.dispatch`` fault acts only when the kernel path is taken —
    so a degraded re-execution provably avoids the faulted site.
    """
    assert op in ("sum", "min", "max"), op
    assert seg_ids.ndim == 1 and values.shape[0] == seg_ids.shape[0], (
        values.shape, seg_ids.shape)
    shape_ok = values.ndim == 1 and values.dtype in (jnp.float32, jnp.int32)
    if use_kernel is None:
        use_kernel = shape_ok and num_segments <= MAX_SEGMENTS
    elif use_kernel and not shape_ok:
        raise ValueError(
            f"segment_reduce kernel needs 1-D f32/i32 values; got "
            f"shape={values.shape} dtype={values.dtype}. Use "
            f"use_kernel=None for the XLA fallback.")
    if use_kernel and oracle_only():
        use_kernel = False
    out = _segment_reduce_jit(values, seg_ids, num_segments, op, use_kernel)
    return _kernel_fault(out) if use_kernel else out


@functools.partial(jax.jit, static_argnames=("num_segments", "op", "use_kernel"))
def _segment_reduce_jit(values, seg_ids, num_segments, op, use_kernel):
    if use_kernel:
        return segment_reduce_tiles(values, seg_ids, num_segments, op)
    init = ref.seg_init(op, values.dtype)
    out = jnp.full((num_segments,) + values.shape[1:], init, values.dtype)
    # out-of-range ids -> num_segments, dropped by the scatter
    idx = jnp.where((seg_ids >= 0) & (seg_ids < num_segments),
                    seg_ids, num_segments)
    at = out.at[idx]
    scatter = {"sum": at.add, "min": at.min, "max": at.max}[op]
    return scatter(values, mode="drop")


def segment_scan(
    values: jax.Array,
    seg_ids: jax.Array,
    op: str = "sum",
    *,
    inclusive: bool = True,
    use_kernel: bool | None = None,
) -> jax.Array:
    """Segmented running sum/min/max along the row axis (window hot path).

    ``out[i] = op(values[j] for j <= i with seg_ids[j] == seg_ids[i])``
    (strict ``j < i`` when ``inclusive=False``; rows without an in-segment
    predecessor hold the op identity). seg_ids: (n,) int32 contiguous runs
    — the sorted-segment layout ``core/ops_agg`` produces — with trailing
    -1 padding allowed.

    The Pallas kernel (kernels/segment_scan.py) handles 1-D f32/i32
    values; ``use_kernel=False`` forces the XLA ``associative_scan``
    oracle (bit-identical on integer-valued inputs). Auto prefers the
    kernel only where it actually runs AS a kernel: under interpret mode
    (no TPU — tests, CPU CI) the emulated per-block triangular mask is
    far slower than XLA's native scan.
    """
    assert op in ("sum", "min", "max"), op
    assert seg_ids.ndim == 1 and values.shape == seg_ids.shape, (
        values.shape, seg_ids.shape)
    shape_ok = values.ndim == 1 and values.dtype in (jnp.float32, jnp.int32)
    if use_kernel is None:
        use_kernel = shape_ok and not interpret_mode()
    elif use_kernel and not shape_ok:
        raise ValueError(
            f"segment_scan kernel needs 1-D f32/i32 values; got "
            f"shape={values.shape} dtype={values.dtype}. Use "
            f"use_kernel=None for the XLA fallback.")
    if use_kernel and oracle_only():
        use_kernel = False
    out = _segment_scan_jit(values, seg_ids, op, inclusive, use_kernel)
    return _kernel_fault(out) if use_kernel else out


@functools.partial(jax.jit,
                   static_argnames=("op", "inclusive", "use_kernel"))
def _segment_scan_jit(values, seg_ids, op, inclusive, use_kernel):
    if use_kernel:
        return segment_scan_tiles(values, seg_ids, op, inclusive=inclusive)
    return ref.segment_scan_ref(values, seg_ids, op, inclusive)


@functools.partial(jax.jit, static_argnames=("tile", "use_kernel"))
def sort_pairs(
    keys: jax.Array,
    payload: jax.Array,
    *,
    tile: int = DEFAULT_TILE,
    use_kernel: bool | None = None,
):
    """Full ascending (keys, payload) sort.

    Strategy (see kernels/bitonic.py): the Pallas bitonic tile is the
    VMEM-resident leaf sort; arrays larger than one tile fall back to XLA's
    global sort (whose TPU lowering is itself a vectorized merge network).
    `use_kernel=False` forces the XLA path — benchmarks compare the two.
    """
    if use_kernel is None:
        use_kernel = True
    (n,) = keys.shape
    if not use_kernel or n > tile:
        return jax.lax.sort((keys, payload), num_keys=1)
    n_pad = max(next_pow2(n), 256)
    kp = jnp.full((n_pad,), key_max(keys.dtype), keys.dtype).at[:n].set(keys)
    vp = jnp.zeros((n_pad,), payload.dtype).at[:n].set(payload)
    ko, vo = bitonic_sort_tiles(kp, vp, tile=n_pad)
    return ko[:n], vo[:n]
