"""Pallas TPU kernel: segmented reduction — the groupby hot path.

After sort-by-key + boundary detection (core/ops_agg.py), aggregation is a
segmented reduction: ``out[g] = op(values[i] for i where seg_ids[i] == g)``.
Scatter-accumulate (the CPU/GPU idiom) serializes on TPU; the native
formulation — same design as kernels/histogram.py — is a one-hot compare
against the segment iota, reduced over the row axis. For f32 sums the
one-hot contraction is a matmul, so the accumulation rides the MXU; min/max
use a masked VPU reduction.

The grid is 2-D: ``(segment tiles, row blocks)``. Each step folds one row
block's partials into the current (1, SEG_TILE)-wide slice of the output;
the row axis is the *inner* grid dimension, so a given output tile stays
VMEM-resident across all of its row steps (HBM sees the rows once per
segment tile and one write per output tile). Within a step, each 128-row
line of the block builds its own (128, SEG_TILE) one-hot from the
transposed block. ``MAX_SEGMENTS`` is the per-tile width budget, not a
limit on the total segment count: larger ``num_segments`` simply adds
segment tiles, each comparing against its own offset window of the
segment id space — but every tile re-reads all rows, so the cost grows as
rows x segments and ``kernels/ops.py`` routes counts above it to the XLA
scatter, which is also the oracle/fallback for N-D payloads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.utils import interpret_mode, round_up

LANES = 128
BLOCK_ROWS = 8  # (8, 128) = 1024 rows per grid step
# per-tile segment width (VMEM budget for the one-hot), NOT a global cap:
# num_segments beyond it tiles the segment axis in the second grid dim
MAX_SEGMENTS = 1024

OPS = ("sum", "min", "max")


def _seg_kernel(seg_ref, val_ref, o_ref, *, op: str, seg_tile: int):
    row_step = pl.program_id(1)  # inner dim: output tile stays resident
    seg_base = pl.program_id(0) * seg_tile
    init = ref.seg_init(op, o_ref.dtype)

    @pl.when(row_step == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, init)

    seg, val = seg_ref[...], val_ref[...]  # (BLOCK_ROWS, LANES)
    # Mosaic cannot flatten a (rows, 128) block into a column, but it can
    # transpose it: column r of seg.T is row r of the block, with its 128
    # rows of the table on sublanes — the one-hot's row axis.
    seg_t, val_t = seg.T, val.T  # (LANES, BLOCK_ROWS)
    # this tile covers segment ids [seg_base, seg_base + seg_tile)
    buckets = jax.lax.broadcasted_iota(jnp.int32, (1, seg_tile), 1) + seg_base
    acc = o_ref[...]
    for r in range(seg.shape[0]):
        # (128, tile) one-hot; padding (-1) matches no bucket
        onehot = seg_t[:, r:r + 1] == buckets
        if op == "sum" and val.dtype == jnp.float32:
            # MXU path: (1, 128) @ (128, tile)
            acc += jnp.dot(val[r:r + 1, :], onehot.astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
            continue
        part = jnp.where(onehot, val_t[:, r:r + 1], init)
        if op == "sum":
            acc += jnp.sum(part, axis=0, keepdims=True)
        elif op == "min":
            acc = jnp.minimum(acc, jnp.min(part, axis=0, keepdims=True))
        elif op == "max":
            acc = jnp.maximum(acc, jnp.max(part, axis=0, keepdims=True))
        else:
            raise ValueError(op)
    o_ref[...] = acc


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "op", "interpret"))
def segment_reduce_tiles(
    values: jax.Array,
    seg_ids: jax.Array,
    num_segments: int,
    op: str = "sum",
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Segmented sum/min/max of 1-D `values` into `num_segments` slots.

    seg_ids: (n,) int32; entries outside [0, num_segments) are ignored.
    Empty segments hold the op identity (0 / +inf-like / -inf-like).
    Any segment count is supported: up to MAX_SEGMENTS runs as a single
    output tile (one VMEM-resident block revisited across row steps);
    beyond that the segment axis tiles into a second grid dimension.
    Matches ref.segment_reduce_ref exactly either way.
    """
    assert op in OPS, op
    assert values.ndim == 1 and values.shape == seg_ids.shape, (
        values.shape, seg_ids.shape)
    if interpret is None:
        interpret = interpret_mode()
    (n,) = values.shape
    tile = BLOCK_ROWS * LANES
    n_pad = max(round_up(n, tile), tile)
    if num_segments <= MAX_SEGMENTS:
        seg_tile = max(round_up(num_segments, LANES), LANES)
    else:
        seg_tile = MAX_SEGMENTS
    g_pad = max(round_up(num_segments, seg_tile), seg_tile)
    segp = jnp.full((n_pad,), -1, jnp.int32).at[:n].set(
        seg_ids.astype(jnp.int32)).reshape(n_pad // LANES, LANES)
    valp = jnp.zeros((n_pad,), values.dtype).at[:n].set(values) \
        .reshape(n_pad // LANES, LANES)
    grid = (g_pad // seg_tile, n_pad // tile)  # (segment tiles, row blocks)
    out = pl.pallas_call(
        functools.partial(_seg_kernel, op=op, seg_tile=seg_tile),
        out_shape=jax.ShapeDtypeStruct((1, g_pad), values.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda s, i: (i, 0)),
                  pl.BlockSpec((BLOCK_ROWS, LANES), lambda s, i: (i, 0))],
        out_specs=pl.BlockSpec((1, seg_tile), lambda s, i: (0, s)),
        interpret=interpret,
    )(segp, valp)
    return out.reshape(g_pad)[:num_segments]
