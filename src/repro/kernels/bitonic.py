"""Pallas TPU kernel: in-VMEM bitonic (key, payload) sort tile.

Cylon's sort-join local operator is bound by the leaf sort. A pointer-based
quicksort/mergesort does not vectorize on the TPU VPU; the TPU-idiomatic
equivalent is a bitonic comparator network: every compare-exchange pass is a
pair of cyclic rolls + compare/select over the whole tile, which maps onto
8x128 vector registers with no data-dependent control flow.

The kernel sorts one tile of TILE (power-of-two) elements entirely in VMEM:
log2(T)*(log2(T)+1)/2 passes, each reading/writing VREGs only — HBM traffic
is one tile read + one tile write total. Arrays larger than one tile go to
XLA's global sort instead (see ops.sort_pairs).

Layout: the tile stays a 2-D (T/128, 128) block; element i sits at row
i // 128, lane i % 128 (Mosaic rejects reshaping the block). A pass at
distance j = 2^p pairs i with i ^ j: a lane roll when j < 128, a sublane
roll otherwise. At stage 2^m, i keeps the smaller of the pair when bits p
and m of i agree (lower element of an ascending block, or upper element of
a descending one), the larger otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import interpret_mode, next_pow2

# 2**11 keys + payload = 2 * 8 KiB * 2 arrays (in+out) ... comfortably < VMEM.
# Kept modest because interpret-mode (CPU CI) executes every pass in Python.
DEFAULT_TILE = 1 << 11
LANES = 128


def _partner(x, dist: int, axis: int, idx):
    """Values of the elements at ``idx ^ dist`` along ``axis`` (``dist`` a
    power of two below the axis length): one of the two cyclic rolls by
    ``dist`` lands each element's partner on it. Which one is read off the
    rolled index itself, so the roll's direction convention cannot matter."""
    size = x.shape[axis]
    a = pltpu.roll(x, dist, axis)
    b = pltpu.roll(x, size - dist, axis)
    from_a = pltpu.roll(idx, dist, axis) == (idx ^ dist)
    return jnp.where(from_a, a, b)


def _bitonic_kernel(k_ref, v_ref, ko_ref, vo_ref):
    keys, vals = k_ref[...], v_ref[...]  # (rows, LANES), kept 2-D
    rows = keys.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    flat = row * LANES + lane  # element index i within the tile
    log_t = (rows * LANES).bit_length() - 1
    # Full static unroll: log_t*(log_t+1)/2 compare-exchange passes.
    for m in range(1, log_t + 1):
        for p in reversed(range(m)):
            j = 1 << p
            # partners i ^ j: across lanes below 128, across rows above
            if j < LANES:
                pk, pv = (_partner(x, j, 1, lane) for x in (keys, vals))
            else:
                pk, pv = (_partner(x, j // LANES, 0, row)
                          for x in (keys, vals))
            # lexicographic (key, payload) comparator: payload tie-break
            # makes the network a stable sort whenever payloads are
            # distinct (callers pass iota)
            le = (keys < pk) | ((keys == pk) & (vals <= pv))
            ge = (keys > pk) | ((keys == pk) & (vals >= pv))
            # i keeps the min when it is the lower element (bit p clear)
            # of an ascending block (bit m clear), or the upper element of
            # a descending one: when bits p and m of i agree
            want_min = (((flat >> p) ^ (flat >> m)) & 1) == 0
            keep = (want_min & le) | (~want_min & ge)
            keys = jnp.where(keep, keys, pk)
            vals = jnp.where(keep, vals, pv)
    ko_ref[...] = keys
    vo_ref[...] = vals


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def bitonic_sort_tiles(
    keys: jax.Array,
    payload: jax.Array,
    *,
    tile: int = DEFAULT_TILE,
    interpret: bool | None = None,
):
    """Sort each contiguous tile of (keys, payload) ascending by key.

    keys: (N,) uint32/int32/float32, N a multiple of `tile` (pow2, >=256).
    Returns per-tile-sorted (keys, payload). Full-array sorts pad with the
    dtype max so the tail tile sorts its sentinels to the end (ops.py).
    """
    if interpret is None:
        interpret = interpret_mode()
    (n,) = keys.shape
    assert n % tile == 0 and tile == next_pow2(tile) and tile >= 256, (n, tile)
    rows = tile // LANES
    kp = keys.reshape(n // LANES, LANES)
    vp = payload.reshape(n // LANES, LANES)
    grid = (n // tile,)
    ko, vo = pl.pallas_call(
        _bitonic_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(kp.shape, keys.dtype),
            jax.ShapeDtypeStruct(vp.shape, payload.dtype),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        ),
        interpret=interpret,
    )(kp, vp)
    return ko.reshape(n), vo.reshape(n)
