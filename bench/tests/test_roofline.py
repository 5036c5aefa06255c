"""The bytes model and the per-call device time of ``bench.roofline``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh
from jax.sharding import PartitionSpec as P

from bench import roofline, tracing
from repro.kernels import ops as kops

N, G = 1000, 16


def _toy(use_kernel):
    def f(x, v, seg):
        return (kops.hash32(x),
                kops.segment_reduce(v, seg, G, "sum", use_kernel=use_kernel))
    return jax.jit(f)


def _args(n=N):
    return (jnp.arange(n, dtype=jnp.int32), jnp.ones(n, jnp.float32),
            jnp.arange(n, dtype=jnp.int32) % G)


@pytest.mark.parametrize("use_kernel", [True, False])
def test_bytes_are_the_calls_operands_and_results(use_kernel):
    got = roofline.call_bytes(_toy(use_kernel).trace(*_args()).jaxpr)
    # hash32: int32 keys in, uint32 hashes out
    assert got["hash32"] == 4 * N + 4 * N
    # values and ids in, one sum per segment out, whichever route runs
    assert got["_segment_reduce_jit"] == 4 * N + 4 * N + 4 * G


def test_a_call_whose_result_is_dropped_counts_nothing():
    """A shuffle hashes its rows to partitions and throws the histogram
    away, then takes one again: the compiler removes the dead call, and
    only the live one counts, at any depth."""
    p = 4

    def inner(x):
        pid = kops.hash32(x) % p
        return pid, kops.bucket_histogram(pid, p)

    def shuffle(x):
        pid, _ = jax.jit(inner)(x)
        return pid, kops.bucket_histogram(pid, p)

    x = _args()[0]
    got = roofline.call_bytes(jax.jit(shuffle).trace(x).jaxpr)
    # one live histogram: uint32 ids in, p int32 counts out
    assert got["hash32"] == 8 * N
    assert got["bucket_histogram"] == 4 * N + 4 * p
    # nothing used at all: nothing counted
    dead = jax.jit(lambda x: (kops.bucket_histogram(x, p), x)[1])
    assert roofline.call_bytes(dead.trace(x).jaxpr) == {}


def test_a_loop_counts_only_a_fixed_trip_count():
    def scanned(x):
        def body(c, _):
            return c, kops.hash32(x)
        return jax.lax.scan(body, 0, None, length=3)[1]

    def looped(x, n):
        return jax.lax.fori_loop(0, n, lambda i, h: h ^ kops.hash32(x),
                                 jnp.zeros(x.shape, jnp.uint32))

    def branched(x, p):
        return jax.lax.cond(p, lambda: kops.hash32(x),
                            lambda: jnp.zeros(x.shape, jnp.uint32))

    x = _args()[0]
    assert roofline.call_bytes(jax.jit(scanned).trace(x).jaxpr) == {
        "hash32": 3 * 8 * N}
    assert roofline.call_bytes(jax.jit(looped).trace(x, 3).jaxpr) == {
        "hash32": None}
    assert roofline.call_bytes(jax.jit(branched).trace(x, True).jaxpr) == {
        "hash32": None}


def test_bytes_are_per_device_inside_shard_map():
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("d",),
                axis_types=(AxisType.Auto,))
    sharded = jax.jit(jax.shard_map(kops.hash32, mesh=mesh, in_specs=P("d"),
                                    out_specs=P("d"), check_vma=False))
    x = _args(4 * N)[0]
    assert roofline.call_bytes(sharded.trace(x).jaxpr, 4) == {
        "hash32": 8 * N}
    # outside shard_map over several devices a call's share is not known
    assert roofline.call_bytes(jax.jit(kops.hash32).trace(x).jaxpr, 4) == {
        "hash32": None}


def test_add_bytes_keeps_an_unknown_count_unknown():
    total = roofline.add_bytes({}, {"a": 10, "b": None}, 3)
    assert total == {"a": 30, "b": None}
    assert roofline.add_bytes(total, {"a": 1, "b": 5}) == {"a": 31,
                                                           "b": None}


def test_compiled_names_and_seconds_per_call():
    text = _toy(False).lower(*_args()).compile().as_text()
    calls = roofline.hlo_calls(text)
    names = {n for ns in calls.values() for n in ns}
    assert {"hash32", "_segment_reduce_jit"} <= names
    h = next(k for k, v in calls.items() if v[-1] == "hash32")
    s = next(k for k, v in calls.items() if v[0] == "_segment_reduce_jit")
    rec = {"ops": {"/device:CPU:0": [[f"%{h} = u32[8] x()", 10, 30],
                                     [f"%{s} = f32[8] y()", 20, 50],
                                     [f"%{h} = u32[8] x()", 25, 40]],
                   "/device:CPU:1": [[f"%{h} = u32[8] x()", 0, 10]]},
           "spans": [["bench.window", 0, 100]]}
    got = roofline.call_seconds(rec, {"jit_f": calls})
    # the union per device ([10, 40] and [0, 10]), then the mean
    assert got["hash32"] == pytest.approx(20e-9)
    assert got["_segment_reduce_jit"] == pytest.approx(15e-9)


def _summary(**kw):
    base = dict(window_s=1.0, queries=1, devices=1, busy_s=1.0,
                category_s={}, alltoall_exposed_s=0.0, span_s={},
                device_ops=[], idle_gaps=[])
    return tracing.Summary(**base, **kw)


def test_share_of_the_roofline():
    s = _summary(call_s={"k": 2.0, "u": 1.0, "z": 1.0},
                 call_bytes={"k": 819e9, "u": None}, hbm_bytes_per_s=819e9)
    assert roofline.share(s, "k") == pytest.approx(50.0)
    # bytes not fixed, no bytes counted, never ran: no reading, never 0
    assert roofline.share(s, "u") is None
    assert roofline.share(s, "z") is None
    assert roofline.share(s, "absent") is None
    no_peak = _summary(call_s={"k": 2.0}, call_bytes={"k": 8})
    assert roofline.share(no_peak, "k") is None
