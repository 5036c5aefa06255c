"""Compile every main-path Pallas kernel for a described TPU v5e.

Interpret mode (the CPU tests) cannot see what Mosaic refuses: shape casts,
scalar stores, tiling. These tests compile each kernel for a v5e that is
described, not attached, at the row counts ``chip_smoke.py`` runs (2^25
rows per chip), and check that the compiled program holds the kernel. The
topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitonic import DEFAULT_TILE, bitonic_sort_tiles
from repro.kernels.hash64 import hash32
from repro.kernels.histogram import bucket_histogram
from repro.kernels.segment_reduce import segment_reduce_tiles
from repro.kernels.segment_scan import segment_scan_tiles

ROWS = 1 << 25  # orders rows per chip in chip_smoke.py


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _col(sharding, dtype, n=ROWS):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def test_hash32_compiles(one_chip):
    text = _compiled_text(lambda x: hash32(x, seed=5, interpret=False),
                          _col(one_chip, jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("buckets", [1, 4])
def test_bucket_histogram_compiles(one_chip, buckets):
    text = _compiled_text(
        lambda ids: bucket_histogram(ids, buckets, interpret=False),
        _col(one_chip, jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("segments", [64, 1024])
@pytest.mark.parametrize("op,dtype", [("sum", jnp.float32),
                                      ("min", jnp.int32)])
def test_segment_reduce_compiles(one_chip, segments, op, dtype):
    text = _compiled_text(
        lambda v, s: segment_reduce_tiles(v, s, segments, op,
                                          interpret=False),
        _col(one_chip, dtype), _col(one_chip, jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op,dtype", [("sum", jnp.float32),
                                      ("max", jnp.int32)])
def test_segment_scan_compiles(one_chip, op, dtype):
    text = _compiled_text(
        lambda v, s: segment_scan_tiles(v, s, op, interpret=False),
        _col(one_chip, dtype), _col(one_chip, jnp.int32))
    assert "tpu_custom_call" in text


def test_bitonic_tile_compiles(one_chip):
    text = _compiled_text(
        lambda k, v: bitonic_sort_tiles(k, v, tile=DEFAULT_TILE,
                                        interpret=False),
        _col(one_chip, jnp.uint32, DEFAULT_TILE),
        _col(one_chip, jnp.int32, DEFAULT_TILE))
    assert "tpu_custom_call" in text
