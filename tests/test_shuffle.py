"""Staged-shuffle unit tests (single-device, subprocess-free).

The pipelined AllToAll's contracts that don't need an 8-device world:
chunking edge cases (non-divisible widths, S=1, S > capacity clamping),
the cost model's stage pick, canonical-key stability (S=1 and default
plans must hit the exact pre-staging cache entries), the empty-table
pack/repartition guards, and bit-identity of every (stages, shuffle_mode)
on a 1-device mesh — including the N-D counts-carrier path and the
no-4-byte-column fallback. The skew/overflow and multi-device identity
checks live in dist_cases (``staged_shuffle``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as PL
from repro.core import stats as S
from repro.core.repartition import (_chunk_bounds, _counts_carrier,
                                    pack_by_partition, repartition,
                                    staged_all_to_all)
from repro.core.table import Table
from repro.utils import shard_map


# --- chunking -----------------------------------------------------------------


def test_chunk_bounds_cover_exactly_once():
    for width in (1, 2, 5, 7, 8, 64, 100):
        for stages in (1, 2, 3, 4, 7, 64, 200):
            bounds = _chunk_bounds(width, stages)
            assert bounds[0][0] == 0 and bounds[-1][1] == width
            for (_, hi), (lo2, _) in zip(bounds, bounds[1:]):
                assert hi == lo2  # contiguous, no overlap, no gap
            assert len(bounds) <= min(stages, width)


def test_chunk_bounds_edges():
    assert _chunk_bounds(0, 4) == []
    assert _chunk_bounds(10, 1) == [(0, 10)]
    assert _chunk_bounds(10, 0) == [(0, 10)]
    # non-divisible width: remainder in the last chunk
    assert _chunk_bounds(10, 3) == [(0, 4), (4, 8), (8, 10)]
    # S > width clamps to one slot per chunk
    assert _chunk_bounds(3, 100) == [(0, 1), (1, 2), (2, 3)]


def test_staged_all_to_all_rejects_unknown_mode():
    with pytest.raises(ValueError):
        staged_all_to_all(jnp.zeros((1, 4)), "x", shuffle_mode="butterfly")


# --- cost-model stage pick ----------------------------------------------------


def test_pick_stages_threshold_and_cap():
    thr = S.STAGE_WIRE_THRESHOLD
    assert S.pick_stages(0, 64) == 1
    assert S.pick_stages(thr, 64) == 1          # at the threshold: still 1
    assert S.pick_stages(thr + 1, 64) == 2
    assert S.pick_stages(4 * thr, 64) == 4
    assert S.pick_stages(1 << 40, 64) == S.MAX_SHUFFLE_STAGES
    # clamped so every chunk keeps >= 1 capacity slot
    assert S.pick_stages(1 << 40, 3) == 3
    assert S.pick_stages(1 << 40, 1) == 1


# --- canonical plan keys ------------------------------------------------------


def test_stage_knobs_at_identity_keep_canonical_key():
    base = PL.Sort(PL.Scan(0), ("k",))
    assert PL.canonical_key(base) == PL.canonical_key(
        PL.Sort(PL.Scan(0), ("k",), stages=1))
    assert PL.canonical_key(base) == PL.canonical_key(
        PL.Sort(PL.Scan(0), ("k",), stages=None))
    assert PL.canonical_key(base) == PL.canonical_key(
        PL.Sort(PL.Scan(0), ("k",), shuffle_mode="alltoall"))


def test_stage_knobs_off_identity_change_canonical_key():
    base = PL.canonical_key(PL.Sort(PL.Scan(0), ("k",)))
    assert base != PL.canonical_key(PL.Sort(PL.Scan(0), ("k",), stages=2))
    assert base != PL.canonical_key(
        PL.Sort(PL.Scan(0), ("k",), shuffle_mode="ring"))


# --- empty-table guards -------------------------------------------------------


def test_pack_by_partition_empty_input():
    send_idx, hist = pack_by_partition(jnp.zeros((0,), jnp.int32), 4, 8)
    assert send_idx.shape == (4, 8) and bool(jnp.all(send_idx == -1))
    assert hist.shape == (4,) and bool(jnp.all(hist == 0))


# --- single-device repartition bit-identity -----------------------------------


def _mesh1():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))


def _repart(table, pid, bucket, **kw):
    mesh = _mesh1()
    P = jax.sharding.PartitionSpec

    def body(t):
        out, st = repartition(t, pid, axis_name="x", bucket_capacity=bucket,
                              **kw)
        return out.columns, out.row_count, st.overflow, st.received

    with mesh:
        return jax.jit(shard_map(body, mesh=mesh, in_specs=(P(),),
                                 out_specs=P()))(table)


def test_repartition_empty_table():
    t = Table({"k": jnp.zeros((0,), jnp.int32),
               "v": jnp.zeros((0, 2), jnp.float32)},
              jnp.asarray(0, jnp.int32))
    cols, rc, ov, recv = _repart(t, jnp.zeros((0,), jnp.int32), 4, stages=2)
    assert int(rc) == 0 and int(ov) == 0 and int(recv) == 0
    assert cols["k"].shape == (4,) and cols["v"].shape == (4, 2)


def test_repartition_stagings_bit_identical():
    # "a" sorts before "k": the 2-D int32 payload is the counts carrier,
    # exercising the N-D meta-slot pack/unpack
    n = 24
    t = Table({"a": jnp.arange(2 * n, dtype=jnp.int32).reshape(n, 2) * 3,
               "k": jnp.arange(n, dtype=jnp.int32)},
              jnp.asarray(n, jnp.int32))
    pid = jnp.zeros((n,), jnp.int32)
    runs = {name: _repart(t, pid, 10, **kw)  # bucket 10 < 24 rows: overflow
            for name, kw in (("s1", dict(stages=1)),
                             ("s3", dict(stages=3)),       # 10 % 3 != 0
                             ("s99", dict(stages=99)),     # clamps to 10
                             ("ring", dict(shuffle_mode="ring")))}
    c1, rc1, ov1, recv1 = runs["s1"]
    assert int(ov1) == n - 10 and int(recv1) == 10
    for name, (c, rc, ov, recv) in runs.items():
        assert int(rc) == int(rc1) and int(ov) == int(ov1), name
        for col in c1:
            assert bool(jnp.all(c[col] == c1[col])), (name, col)


@pytest.mark.parametrize("columns,carrier", [
    ({"a": jnp.float32, "k": jnp.int32}, "k"),
    ({"a": jnp.uint32, "k": jnp.int32}, "a"),
    ({"a": jnp.float32, "b": jnp.uint8}, None),
])
def test_counts_carrier_is_a_4byte_integer_column(columns, carrier):
    # counts bitcast into a float column would be subnormal below 2^23,
    # and the TPU flushes subnormals to zero: floats never carry them
    t = Table({name: jnp.zeros((4,), dtype) for name, dtype in columns.items()},
              jnp.asarray(4, jnp.int32))
    assert _counts_carrier(t) == carrier


def test_repartition_counts_fallback_without_4byte_column():
    # no 4-byte column -> the separate counts exchange (carrier None)
    n = 8
    t = Table({"b": jnp.arange(n, dtype=jnp.uint8)}, jnp.asarray(n, jnp.int32))
    pid = jnp.zeros((n,), jnp.int32)
    c1, rc1, ov1, _ = _repart(t, pid, n, stages=1)
    c2, rc2, ov2, _ = _repart(t, pid, n, stages=2)
    assert int(rc1) == int(rc2) == n and int(ov1) == int(ov2) == 0
    assert bool(jnp.all(c1["b"] == c2["b"]))
