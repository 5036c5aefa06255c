"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU).

Assignment contract: for each kernel, sweep shapes/dtypes and
assert_allclose against the ref.py oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.bitonic import bitonic_sort_tiles
from repro.kernels.flash_attention import flash_attention
from repro.kernels.hash64 import hash32
from repro.kernels.histogram import bucket_histogram

RNG = np.random.default_rng(0)


# --- hash32 -----------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 7, 128, 8192, 8193, 100_000])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.uint32, jnp.float32])
def test_hash32_sweep(n, dtype):
    if dtype == jnp.float32:
        x = jnp.asarray(RNG.standard_normal(n), dtype)
    else:
        x = jnp.asarray(RNG.integers(-2**31, 2**31 - 1, n), jnp.int64) \
            .astype(dtype)
    got = hash32(x, seed=17)
    want = ref.hash32_ref(x, seed=17)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_hash32_seed_sensitivity():
    x = jnp.arange(100, dtype=jnp.int32)
    a = np.asarray(hash32(x, seed=0))
    b = np.asarray(hash32(x, seed=1))
    assert (a != b).mean() > 0.99


def test_hash_columns_multicolumn():
    a = jnp.asarray(RNG.integers(0, 100, 50), jnp.int32)
    b = jnp.asarray(RNG.integers(0, 100, 50), jnp.int32)
    h_ab = np.asarray(kops.hash_columns([a, b]))
    h_ba = np.asarray(kops.hash_columns([b, a]))
    assert (h_ab != h_ba).any()  # order-sensitive


# --- histogram ----------------------------------------------------------------


@pytest.mark.parametrize("n,buckets", [(1, 2), (100, 7), (5000, 16),
                                       (4096, 256), (9999, 64)])
def test_histogram_sweep(n, buckets):
    ids = jnp.asarray(RNG.integers(-1, buckets, n), jnp.int32)
    got = bucket_histogram(ids, buckets)
    want = ref.histogram_ref(ids, buckets)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(np.asarray(got).sum()) == int((np.asarray(ids) >= 0).sum())


# --- segment reduce: segment-axis tiling across the one-tile boundary ----------


@pytest.mark.parametrize("n,g", [
    (3000, 1023),   # just under one tile (single output block, old path)
    (3000, 1024),   # exactly one tile
    (3000, 1025),   # first tiled case: 2 segment tiles
    (9999, 2048),   # tile-aligned multi-tile
    (5000, 3000),   # ragged final tile
])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_segment_reduce_tiled_boundary_sweep(n, g, op, dtype):
    from repro.kernels.segment_reduce import MAX_SEGMENTS, segment_reduce_tiles
    assert MAX_SEGMENTS == 1024  # the sweep brackets this boundary
    vals = jnp.asarray(RNG.integers(-40, 40, n), dtype)
    seg = jnp.asarray(RNG.integers(-1, g, n), jnp.int32)  # -1 = padding
    want = np.asarray(ref.segment_reduce_ref(vals, seg, g, op))
    got = np.asarray(segment_reduce_tiles(vals, seg, g, op))
    np.testing.assert_array_equal(got, want)
    # the public wrapper routes oversize counts to the SAME kernel now;
    # the XLA scatter path stays available as the use_kernel=False oracle
    via_ops = np.asarray(kops.segment_reduce(vals, seg, g, op,
                                             use_kernel=True))
    fallback = np.asarray(kops.segment_reduce(vals, seg, g, op,
                                              use_kernel=False))
    np.testing.assert_array_equal(via_ops, want)
    np.testing.assert_array_equal(fallback, want)


def test_segment_reduce_tiled_values_land_in_correct_tile():
    # one value per segment, segments chosen to straddle every tile edge:
    # any offset error between tiles would misplace them
    from repro.kernels.segment_reduce import MAX_SEGMENTS, segment_reduce_tiles
    g = 3 * MAX_SEGMENTS
    targets = np.asarray([0, MAX_SEGMENTS - 1, MAX_SEGMENTS,
                          2 * MAX_SEGMENTS - 1, 2 * MAX_SEGMENTS, g - 1],
                         np.int32)
    vals = jnp.asarray(np.arange(1, len(targets) + 1), jnp.int32)
    out = np.asarray(segment_reduce_tiles(vals, jnp.asarray(targets), g,
                                          "sum"))
    expect = np.zeros((g,), np.int32)
    expect[targets] = np.arange(1, len(targets) + 1)
    np.testing.assert_array_equal(out, expect)


@pytest.mark.parametrize("tpu", [False, True])
def test_segment_reduce_auto_routing_ignores_backend(monkeypatch, tpu):
    # auto takes the one-hot kernel up to one segment tile and the XLA
    # scatter beyond it on EVERY backend: past one tile the kernel re-reads
    # every row per tile (rows x segments). The backend is steered here,
    # and the executor is a spy, so no kernel runs.
    import repro.utils
    from repro.kernels.segment_reduce import MAX_SEGMENTS

    monkeypatch.setattr(repro.utils, "on_tpu", lambda: tpu)
    picked = []

    def spy(values, seg_ids, num_segments, op, use_kernel):
        picked.append(use_kernel)
        return jnp.zeros((num_segments,), values.dtype)

    monkeypatch.setattr(kops, "_segment_reduce_jit", spy)
    vals = jnp.ones((64,), jnp.float32)
    seg = jnp.zeros((64,), jnp.int32)
    for g in (MAX_SEGMENTS, MAX_SEGMENTS + 1, 1 << 20):
        kops.segment_reduce(vals, seg, g, "sum")
    assert picked == [True, False, False]


# --- segment reduce: float32 sums stay accurate however long a segment ------


def _rel_err(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float64) - want)
                        / np.abs(want)))


@pytest.mark.parametrize("use_kernel", [True, False])
def test_segment_reduce_float_sum_does_not_stall(use_kernel):
    # 2**24 and then 2**20 ones in one segment: a sum that adds the rows
    # one after another stays at 2**24 (each 1 is half an ulp there)
    vals = np.concatenate([[2.0 ** 24], np.ones(2 ** 20)]).astype(np.float32)
    seg = np.zeros(vals.shape, np.int32)
    got = kops.segment_reduce(jnp.asarray(vals), jnp.asarray(seg), 4, "sum",
                              use_kernel=use_kernel)
    want = vals.astype(np.float64).sum()
    assert _rel_err(np.asarray(got)[:1], want) <= 1e-6
    np.testing.assert_array_equal(np.asarray(got)[1:], 0.0)


@pytest.mark.parametrize("use_kernel", [True, False])
def test_segment_reduce_float_sum_q1_magnitudes(use_kernel):
    # 2**21 shuffled rows in 4 groups at TPC-H Q1's extendedprice scale:
    # each group's float32 sum within 1e-6 of the float64 sum
    rng = np.random.default_rng(17)
    n = 2 ** 21
    group = rng.integers(0, 4, n).astype(np.int32)
    price = np.round(rng.integers(1, 51, n) * rng.uniform(900.0, 2100.0, n),
                     2).astype(np.float32)
    got = kops.segment_reduce(jnp.asarray(price), jnp.asarray(group), 4,
                              "sum", use_kernel=use_kernel)
    want = np.bincount(group, weights=price.astype(np.float64), minlength=4)
    assert _rel_err(got, want) <= 1e-6


# --- segment scan: carry across the row-block (1024) boundary -------------------


@pytest.mark.parametrize("n", [
    1,        # single row
    1023,     # one row short of a block
    1024,     # exactly one block
    1025,     # first carried case: 2 blocks, segment spans the edge
    3000,     # ragged multi-block
])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("inclusive", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_segment_scan_block_boundary_sweep(n, op, inclusive, dtype):
    from repro.kernels.segment_scan import BLOCK, segment_scan_tiles
    assert BLOCK == 1024  # the sweep brackets this boundary
    # contiguous non-decreasing runs, ids sparse (skipped ids = empty
    # segments), run lengths down to 1 (single-row segments)
    seg = np.sort(RNG.integers(0, max(1, n // 2), n) * 3).astype(np.int32)
    vals = jnp.asarray(RNG.integers(-40, 40, n), dtype)
    segj = jnp.asarray(seg)
    want = np.asarray(ref.segment_scan_ref(vals, segj, op, inclusive))
    got = np.asarray(segment_scan_tiles(vals, segj, op, inclusive=inclusive))
    np.testing.assert_array_equal(got, want)
    # the public wrapper: forced kernel and forced oracle both match
    via_ops = np.asarray(kops.segment_scan(vals, segj, op,
                                           inclusive=inclusive,
                                           use_kernel=True))
    fallback = np.asarray(kops.segment_scan(vals, segj, op,
                                            inclusive=inclusive,
                                            use_kernel=False))
    np.testing.assert_array_equal(via_ops, want)
    np.testing.assert_array_equal(fallback, want)


def test_segment_scan_single_segment_spans_blocks():
    # ONE segment over 3 blocks: any carry bug accumulates visibly
    from repro.kernels.segment_scan import BLOCK, segment_scan_tiles
    n = 3 * BLOCK
    vals = jnp.ones((n,), jnp.int32)
    seg = jnp.zeros((n,), jnp.int32)
    got = np.asarray(segment_scan_tiles(vals, seg, "sum"))
    np.testing.assert_array_equal(got, np.arange(1, n + 1))
    excl = np.asarray(segment_scan_tiles(vals, seg, "sum", inclusive=False))
    np.testing.assert_array_equal(excl, np.arange(n))


def test_segment_scan_boundary_straddling_runs():
    # segments chosen to cut exactly AT the block edges (1024±1): a new
    # segment beginning at the first row of a block must ignore the carry
    from repro.kernels.segment_scan import BLOCK, segment_scan_tiles
    n = 2 * BLOCK + 2
    edges = [0, BLOCK - 1, BLOCK, BLOCK + 1, 2 * BLOCK, n]
    seg = np.zeros((n,), np.int32)
    for s_id, (lo, hi) in enumerate(zip(edges, edges[1:])):
        seg[lo:hi] = s_id
    vals = jnp.asarray(RNG.integers(-9, 9, n), jnp.int32)
    segj = jnp.asarray(seg)
    for op in ("sum", "min", "max"):
        want = np.asarray(ref.segment_scan_ref(vals, segj, op, True))
        got = np.asarray(segment_scan_tiles(vals, segj, op))
        np.testing.assert_array_equal(got, want)


def test_segment_scan_rejects_bad_shapes():
    vals = jnp.zeros((8, 2), jnp.float32)
    seg = jnp.zeros((8,), jnp.int32)
    with pytest.raises(Exception):
        kops.segment_scan(vals, seg, "sum", use_kernel=True)


# --- bitonic sort ---------------------------------------------------------------


@pytest.mark.parametrize("n", [256, 512, 2048])
@pytest.mark.parametrize("dtype", [jnp.uint32, jnp.int32, jnp.float32])
def test_bitonic_tile_sorted(n, dtype):
    if dtype == jnp.float32:
        keys = jnp.asarray(RNG.standard_normal(n), dtype)
    else:
        keys = jnp.asarray(RNG.integers(0, 10_000, n), dtype)
    payload = jnp.arange(n, dtype=jnp.int32)
    ko, vo = bitonic_sort_tiles(keys, payload, tile=n)
    kr, vr = ref.sort_pairs_ref(keys, payload)
    np.testing.assert_array_equal(np.asarray(ko), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(vr))


@pytest.mark.parametrize("n", [10, 300, 1000])
def test_sort_pairs_wrapper(n):
    keys = jnp.asarray(RNG.integers(0, 50, n), jnp.uint32)  # dups: stability
    payload = jnp.arange(n, dtype=jnp.int32)
    ko, vo = kops.sort_pairs(keys, payload)
    kr, vr = ref.sort_pairs_ref(keys, payload)
    np.testing.assert_array_equal(np.asarray(ko), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(vr))


# --- flash attention -------------------------------------------------------------


@pytest.mark.parametrize("shape", [
    # (B, S, H, KV, hd, bq, bk)
    (2, 256, 4, 2, 64, 128, 128),
    (1, 512, 8, 8, 32, 256, 128),
    (1, 256, 4, 1, 128, 128, 256),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(shape, causal):
    b, s, h, kv, hd, bq, bk = shape
    q = jnp.asarray(RNG.standard_normal((b, s, h, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, kv, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, kv, hd)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    b, s, h, kv, hd = 1, 256, 4, 2, 64
    q = jnp.asarray(RNG.standard_normal((b, s, h, hd)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((b, s, kv, hd)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((b, s, kv, hd)), jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, bq=128, bk=128)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-2, rtol=2e-2)


# --- model-layer chunked attention vs flash kernel (cross-validation) -----------


def test_chunked_sdpa_matches_flash_kernel():
    from repro.models import layers as NN
    from repro.models.common import ModelConfig
    cfg = ModelConfig(arch="x", family="dense", num_layers=1, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      time_unroll=True)
    b, s, h, kv, hd = 1, 256, 4, 2, 64
    q = jnp.asarray(RNG.standard_normal((b, s, h, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, kv, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, kv, hd)), jnp.float32)
    # force both chunked paths
    ch_q = NN._chunked_q(q, NN._repeat_kv(k, 2), NN._repeat_kv(v, 2),
                         causal=True, q_offset=0, kv_len=None, cfg=cfg)
    ch_k = NN._chunked_k(q, NN._repeat_kv(k, 2), NN._repeat_kv(v, 2),
                         causal=True, q_offset=0, kv_len=None, cfg=cfg)
    want = flash_attention(q, k, v, causal=True, bq=128, bk=128)
    np.testing.assert_allclose(np.asarray(ch_q), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(ch_k), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
