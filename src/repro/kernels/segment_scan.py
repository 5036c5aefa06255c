"""Pallas TPU kernel: segmented prefix scan — the window-function hot path.

Window functions (core/ops_agg.window) reduce to *segment scans* over the
sorted frame: after sort-by-(keys, order) + boundary detection, ``rank`` is
a segmented running max, ``dense_rank``/``cumsum``/``running_mean`` are
segmented running sums, ``cummax`` a running max — all over contiguous
per-group runs of rows.

The kernel formulation mirrors kernels/segment_reduce.py's one-hot idiom,
tiled along the segment-sorted row axis: the grid walks (8, 128) row blocks
in order, and for each 128-row line of a block it materializes the
(128, 128) *triangular same-segment* mask — ``mask[j, i] = (j < i) &
(seg[j] == seg[i])`` — so the line's exclusive scan is one masked reduction
over the j axis (an MXU matmul for f32 sums, a VPU min/max otherwise). The
j axis comes from the transposed block: Mosaic cannot flatten a block into
a column. Lines are scanned in order, and TPU grid steps execute
sequentially, so the carry (the running value and segment id of the last
row scanned) threads every line of every block; across grid steps it lives
in VMEM scratch, broadcast over the lanes.

Requirements: segment ids form contiguous runs (non-decreasing, as
produced by sort + cumsum-of-boundaries), with -1 allowed as trailing
padding. ``ref.segment_scan_ref`` (jax.lax.associative_scan over
(segment, value) pairs) is the bit-exact oracle under integer or
integer-valued-float inputs; kernels/ops.py routes ``use_kernel=False``
(and CPU interpret mode, where the emulated triangular mask is far slower
than XLA's scan) to it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.utils import interpret_mode, round_up

LANES = 128
BLOCK_ROWS = 8
#: rows per grid step; each of its 8 lines builds one (128, 128) mask
BLOCK = BLOCK_ROWS * LANES  # 1024

OPS = ("sum", "min", "max")


def _scan_kernel(seg_ref, val_ref, o_ref, cval_ref, cseg_ref, *,
                 op: str, inclusive: bool):
    step = pl.program_id(0)
    init = ref.seg_init(op, o_ref.dtype)

    @pl.when(step == 0)
    def _init():
        cval_ref[...] = jnp.full_like(cval_ref, init)
        # -2 matches no real segment id (>= 0) and no -1 padding
        cseg_ref[...] = jnp.full_like(cseg_ref, -2)

    seg, val = seg_ref[...], val_ref[...]  # (BLOCK_ROWS, LANES)
    # transposed copies put one block row's 128 table rows on sublanes: the
    # predecessor axis j of the mask (Mosaic cannot flatten the block)
    seg_t, val_t = seg.T, val.T
    jj = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    # the carry: running value and segment id of the last row scanned so
    # far, broadcast over the lanes
    cval, cseg = cval_ref[...], cseg_ref[...]  # (1, LANES)
    for r in range(seg.shape[0]):
        s_row, v_row = seg[r:r + 1, :], val[r:r + 1, :]  # (1, LANES)
        # mask[j, i]: row j strictly precedes row i in i's segment run
        mask = (jj < ii) & (seg_t[:, r:r + 1] == s_row)
        if op == "sum" and val.dtype == jnp.float32:
            # MXU path: (1, 128) @ (128, 128)
            excl = jnp.dot(v_row, mask.astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
        else:
            part = jnp.where(mask, val_t[:, r:r + 1], init)
            excl = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[op](
                part, axis=0, keepdims=True)
        # fold the carry into rows continuing its segment
        carry = jnp.where(s_row == cseg, cval, init)
        if op == "sum":
            excl = excl + carry
            incl = excl + v_row
        elif op == "min":
            excl = jnp.minimum(excl, carry)
            incl = jnp.minimum(excl, v_row)
        else:
            excl = jnp.maximum(excl, carry)
            incl = jnp.maximum(excl, v_row)
        o_ref[r:r + 1, :] = incl if inclusive else excl
        cval = jnp.broadcast_to(incl[:, LANES - 1:], cval.shape)
        cseg = jnp.broadcast_to(s_row[:, LANES - 1:], cseg.shape)
    cval_ref[...] = cval
    cseg_ref[...] = cseg


@functools.partial(jax.jit,
                   static_argnames=("op", "inclusive", "interpret"))
def segment_scan_tiles(
    values: jax.Array,
    seg_ids: jax.Array,
    op: str = "sum",
    *,
    inclusive: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Segmented running sum/min/max of 1-D ``values`` along the row axis.

    ``out[i] = op(values[j] for j <= i with seg_ids[j] == seg_ids[i])``
    (``j < i`` when ``inclusive=False``; rows with no in-segment
    predecessor hold the op identity). seg_ids: (n,) int32 contiguous
    runs — non-decreasing, -1 trailing padding allowed. Matches
    ``ref.segment_scan_ref`` exactly on integer-valued inputs.
    """
    assert op in OPS, op
    assert values.ndim == 1 and values.shape == seg_ids.shape, (
        values.shape, seg_ids.shape)
    if interpret is None:
        interpret = interpret_mode()
    (n,) = values.shape
    n_pad = max(round_up(n, BLOCK), BLOCK)
    segp = jnp.full((n_pad,), -1, jnp.int32).at[:n].set(
        seg_ids.astype(jnp.int32)).reshape(n_pad // LANES, LANES)
    valp = jnp.zeros((n_pad,), values.dtype).at[:n].set(values) \
        .reshape(n_pad // LANES, LANES)
    grid = (n_pad // BLOCK,)
    out = pl.pallas_call(
        functools.partial(_scan_kernel, op=op, inclusive=inclusive),
        out_shape=jax.ShapeDtypeStruct((n_pad // LANES, LANES), values.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda s: (s, 0)),
                  pl.BlockSpec((BLOCK_ROWS, LANES), lambda s: (s, 0))],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda s: (s, 0)),
        scratch_shapes=[pltpu.VMEM((1, LANES), values.dtype),  # carry val
                        pltpu.VMEM((1, LANES), jnp.int32)],    # carry seg
        # the carry threads the row blocks in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(segp, valp)
    return out.reshape(n_pad)[:n]
