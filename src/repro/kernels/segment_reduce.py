"""Pallas TPU kernel: segmented reduction — the groupby hot path.

After sort-by-key + boundary detection (core/ops_agg.py), aggregation is a
segmented reduction: ``out[g] = op(values[i] for i where seg_ids[i] == g)``.
Scatter-accumulate (the CPU/GPU idiom) serializes on TPU; the native
formulation — same design as kernels/histogram.py — is a one-hot compare
against the segment iota, reduced over the row axis with a masked VPU
reduction.

The grid is 2-D: ``(segment tiles, row blocks)``. Each step folds one row
block's partials into the current (1, SEG_TILE)-wide slice of the output;
the row axis is the *inner* grid dimension, so a given output tile stays
VMEM-resident across all of its row steps (HBM sees the rows once per
segment tile and one write per output tile). Within a step, each 128-row
line of the block builds its own (128, SEG_TILE) one-hot from the
transposed block, and the block's 1024 masked rows are added as a
pairwise tree. ``MAX_SEGMENTS`` is the per-tile width budget, not a
limit on the total segment count: larger ``num_segments`` simply adds
segment tiles, each comparing against its own offset window of the
segment id space — but every tile re-reads all rows, so the cost grows as
rows x segments and ``kernels/ops.py`` routes counts above it to XLA,
which is also the oracle/fallback for N-D payloads.

Float sums carry a Neumaier compensation tile beside the output tile, as
resident as it: the running sum over row steps loses no more than one
rounding in all, and the tree inside a step rounds log2(1024) times, so a
group's error does not grow with its row count. Integer sums, min and max
are exact in any order.
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


_COMBINE = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}
_FOLD = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}


def _tree(op: str, parts: list):
    """Pairwise reduction of a list of equal-shaped blocks."""
    f = _COMBINE[op]
    while len(parts) > 1:
        pairs = [f(a, b) for a, b in zip(parts[::2], parts[1::2])]
        parts = pairs + parts[len(pairs) * 2:]
    return parts[0]


def _seg_kernel(seg_ref, val_ref, o_ref, *comp_ref, op: str, seg_tile: int):
    row_step = pl.program_id(1)  # inner dim: output tile stays resident
    seg_base = pl.program_id(0) * seg_tile
    init = ref.seg_init(op, o_ref.dtype)

    @pl.when(row_step == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, init)
        for c in comp_ref:
            c[...] = jnp.zeros_like(c)

    seg, val = seg_ref[...], val_ref[...]  # (BLOCK_ROWS, LANES)
    # Mosaic cannot flatten a (rows, 128) block into a column, but it can
    # transpose it: column r of seg.T is row r of the block, with its 128
    # rows of the table on sublanes — the one-hot's row axis.
    seg_t, val_t = seg.T, val.T  # (LANES, BLOCK_ROWS)
    # this tile covers segment ids [seg_base, seg_base + seg_tile)
    buckets = jax.lax.broadcasted_iota(jnp.int32, (1, seg_tile), 1) + seg_base
    f = _COMBINE[op]
    lines = []
    for r in range(seg.shape[0]):
        # (128, tile) masked rows; padding (-1) matches no bucket
        part = jnp.where(seg_t[:, r:r + 1] == buckets, val_t[:, r:r + 1],
                         init)
        half = LANES // 2
        while half >= 8:  # sublane-aligned halves down to (8, tile)
            part = f(part[:half], part[half:])
            half //= 2
        lines.append(part)
    y = _FOLD[op](_tree(op, lines), axis=0, keepdims=True)  # (1, tile)
    acc = o_ref[...]
    if comp_ref:  # Neumaier: carry what each addition rounds away
        (c_ref,) = comp_ref
        t = acc + y
        c_ref[...] += jnp.where(jnp.abs(acc) >= jnp.abs(y),
                                (acc - t) + y, (y - t) + acc)
        o_ref[...] = t
    else:
        o_ref[...] = f(acc, y)


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
    Integer sums, min and max match ref.segment_reduce_ref exactly
    either way; so do float sums of integer-valued inputs whose partial
    sums stay below 2**24. Other float sums are within a few roundings of
    the exact sum, whatever the rows per segment (module docstring).
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
    out_spec = pl.BlockSpec((1, seg_tile), lambda s, i: (0, s))
    out_sds = jax.ShapeDtypeStruct((1, g_pad), values.dtype)
    compensate = op == "sum" and jnp.issubdtype(values.dtype, jnp.floating)
    out = pl.pallas_call(
        functools.partial(_seg_kernel, op=op, seg_tile=seg_tile),
        out_shape=(out_sds,) * (1 + compensate),
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda s, i: (i, 0)),
                  pl.BlockSpec((BLOCK_ROWS, LANES), lambda s, i: (i, 0))],
        out_specs=(out_spec,) * (1 + compensate),
        interpret=interpret,
    )(segp, valp)
    total = out[0] + out[1] if compensate else out[0]
    return total.reshape(g_pad)[:num_segments]
