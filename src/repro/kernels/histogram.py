"""Pallas TPU kernel: bucket histogram for hash-partition (one-hot reduction).

Cylon's hash-partition needs per-destination row counts before building send
buffers. Scatter-add (the CPU/GPU idiom) is serialized on TPU; the native
formulation is a compare + reduction per bucket over dense (rows, 128)
blocks. P is the shard count, so the per-bucket loop is short.

Grid walks row-blocks; each step counts its block once per bucket and
accumulates into the single (1, P) output block (revisited across the
grid — Pallas keeps it resident in VMEM, so HBM sees one read of ids and
one write of P counts). Blocks stay 2-D inside the kernel: Mosaic rejects
flattening a (rows, 128) block to a column.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils import interpret_mode, round_up

LANES = 128
BLOCK_ROWS = 32  # (32, 128) ids per grid step


def _hist_kernel(ids_ref, o_ref, *, num_buckets: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ids = ids_ref[...]  # (BLOCK_ROWS, LANES), kept 2-D for Mosaic
    buckets = jax.lax.broadcasted_iota(jnp.int32, (1, num_buckets), 1)

    # one masked count per bucket (P is the shard count: small); invalid
    # ids (< 0, e.g. padding) match no bucket
    def count(b, acc):
        n = jnp.sum((ids == b).astype(jnp.int32))
        return acc + jnp.where(buckets == b, n, 0)

    o_ref[...] += jax.lax.fori_loop(0, num_buckets, count,
                                    jnp.zeros((1, num_buckets), jnp.int32))


@functools.partial(jax.jit, static_argnames=("num_buckets", "interpret"))
def bucket_histogram(
    ids: jax.Array, num_buckets: int, *, interpret: bool | None = None
) -> jax.Array:
    """Count occurrences of each bucket id in [0, num_buckets).

    ids: (N,) int32; entries outside the range (padding uses -1) are ignored.
    Returns (num_buckets,) int32. Matches ref.histogram_ref exactly.
    """
    if interpret is None:
        interpret = interpret_mode()
    (n,) = ids.shape
    tile = BLOCK_ROWS * LANES
    n_pad = max(round_up(n, tile), tile)
    idp = jnp.full((n_pad,), -1, jnp.int32).at[:n].set(ids.astype(jnp.int32))
    idp = idp.reshape(n_pad // LANES, LANES)
    grid = (n_pad // tile,)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, num_buckets=num_buckets),
        out_shape=jax.ShapeDtypeStruct((1, num_buckets), jnp.int32),
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, num_buckets), lambda i: (0, 0)),
        interpret=interpret,
    )(idp)
    return out.reshape(num_buckets)
