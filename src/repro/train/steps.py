"""Step functions: train (microbatched, optionally pod-compressed grads),
prefill, decode — plus the sharding specs to jit them with.

Compute/comm overlap: gradient accumulation is a ``lax.scan`` over
microbatches, so XLA can overlap microbatch k+1's compute with the
reduce-scatter/all-gather traffic of microbatch k's backward (and the
single post-scan DP all-reduce hides behind the optimizer). Microbatch
slicing is *interleaved* (batch row r belongs to microbatch r mod K) so the
slice is shard-local — no relayout collective (DESIGN.md §6).

Gradient compression (``compress_pod=True``): on multi-pod meshes the
grads crossing the DCN (pod axis) are int8-quantized with per-leaf scales
and **error feedback**: each pod keeps the quantization residual and adds
it to the next step's gradient, so the bias vanishes over steps. Wire
format is an all-gather of (int8 tensor, fp32 scale) over ``pod`` + local
mean — 4x fewer DCN bytes than an fp32 ring all-reduce.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import DATA_AXIS, MODEL_AXIS, POD_AXIS
from repro.models.factory import Model
from repro.train.optimizer import OptConfig, OptState, apply_updates, init_opt, opt_state_specs


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    step: jax.Array
    ef: Any  # error-feedback residuals (int8 pod compression) or None


def init_train_state(model: Model, key, *, compress_pod: bool = False,
                     n_pods: int = 1) -> TrainState:
    params = model.init(key)
    ef = None
    if compress_pod:
        ef = jax.tree.map(
            lambda p: jnp.zeros((n_pods,) + p.shape, jnp.float32), params)
    return TrainState(params=params, opt=init_opt(params),
                      step=jnp.zeros((), jnp.int32), ef=ef)


def master_specs(model: Model):
    """ZeRO specs for fp32 optimizer state + grad accumulator: param specs
    with one extra DATA_AXIS dim sharded (common.fsdp_extend)."""
    from repro.models.common import fsdp_extend
    data = model.rules.data
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return fsdp_extend(model.param_specs, shapes, max(data, 1))


def train_state_specs(model: Model, *, compress_pod: bool = False):
    ps = model.param_specs
    ms = master_specs(model)
    ef = None
    if compress_pod:
        ef = jax.tree.map(lambda s: P(POD_AXIS, *s), ms,
                          is_leaf=lambda x: isinstance(x, P))
    return TrainState(params=ps, opt=opt_state_specs(ms), step=P(), ef=ef)


def batch_specs(model: Model, batch_tree):
    """PartitionSpecs for a batch pytree: batch dim over the DP axes."""
    b = model.rules.batch_axes()
    return jax.tree.map(lambda x: P(b, *([None] * (x.ndim - 1))), batch_tree)


# ---------------------------------------------------------------------------
# gradient accumulation
# ---------------------------------------------------------------------------


def _microbatch(batch, k: jax.Array, num: int):
    """Interleaved microbatch k of `num` — shard-local slicing (row r of the
    global batch belongs to microbatch r mod num)."""
    def slice_one(x):
        b = x.shape[0]
        xr = x.reshape((b // num, num) + x.shape[1:])
        return jax.lax.dynamic_index_in_dim(xr, k, axis=1, keepdims=False)
    return jax.tree.map(slice_one, batch)


def _accumulate_grads(loss_fn, params, batch, num: int, *, mesh=None,
                      acc_specs=None):
    """Mean loss/grads over `num` microbatches via scan (overlap-friendly).

    The fp32 accumulator is constrained to the ZeRO (master) specs so each
    microbatch's gradients are reduce-scattered over DATA_AXIS instead of
    all-reduced (ZeRO-2); memory is params_fp32 / (model*data)."""
    def constrain(g):
        if mesh is None or acc_specs is None:
            return g
        from repro.utils import safe_constrain
        return jax.tree.map(lambda x, s: safe_constrain(x, mesh, s),
                            g, acc_specs)

    if num == 1:
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return constrain(jax.tree.map(
            lambda g: g.astype(jnp.float32), grads)), metrics

    def body(carry, k):
        acc, msum = carry
        mb = _microbatch(batch, k, num)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, mb)
        acc = constrain(jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32), acc, grads))
        msum = jax.tree.map(lambda a, m: a + m.astype(jnp.float32),
                            msum, metrics)
        return (acc, msum), None

    zero_g = constrain(jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params))
    zero_m = jax.eval_shape(lambda p, b: loss_fn(p, b)[1], params,
                            _microbatch(batch, jnp.int32(0), num))
    zero_m = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), zero_m)
    (grads, msum), _ = jax.lax.scan(body, (zero_g, zero_m),
                                    jnp.arange(num, dtype=jnp.int32))
    grads = jax.tree.map(lambda g: g / num, grads)
    metrics = jax.tree.map(lambda m: m / num, msum)
    return grads, metrics


# ---------------------------------------------------------------------------
# int8 error-feedback pod compression
# ---------------------------------------------------------------------------


def _quantize(g):
    s = jnp.max(jnp.abs(g)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(g / s), -127, 127).astype(jnp.int8)
    return q, s


def _pod_compress(grads, ef):
    """Inside shard_map(manual={'pod'}): per-pod grads -> mean of int8
    all-gathered grads; returns (decompressed mean, new residuals)."""
    def one(g, e):
        g = g.astype(jnp.float32) + e
        q, s = _quantize(g)
        deq = q.astype(jnp.float32) * s
        new_e = g - deq
        qg = jax.lax.all_gather(q, POD_AXIS)
        sg = jax.lax.all_gather(s, POD_AXIS)
        shp = (-1,) + (1,) * g.ndim
        mean = jnp.mean(qg.astype(jnp.float32) * sg.reshape(shp), axis=0)
        return mean, new_e

    flat_g, td = jax.tree.flatten(grads)
    flat_e = td.flatten_up_to(ef)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return td.unflatten([o[0] for o in out]), td.unflatten([o[1] for o in out])


# ---------------------------------------------------------------------------
# step factories
# ---------------------------------------------------------------------------


def make_train_step(model: Model, ocfg: OptConfig, *, microbatches: int = 1,
                    compress_pod: bool = False):
    """Returns step_fn(state, batch) -> (state, metrics)."""
    loss_fn = model.loss_fn
    acc_specs = master_specs(model) if model.mesh is not None else None

    if not compress_pod:
        def step_fn(state: TrainState, batch):
            grads, metrics = _accumulate_grads(
                loss_fn, state.params, batch, microbatches, mesh=model.mesh,
                acc_specs=acc_specs)
            params, opt, om = apply_updates(state.params, grads, state.opt,
                                            ocfg)
            return TrainState(params, opt, state.step + 1, state.ef), \
                {**metrics, **om}
        return step_fn

    mesh = model.mesh
    assert mesh is not None and POD_AXIS in mesh.axis_names, \
        "compress_pod needs a multi-pod mesh"

    def pod_body(params, ef_local, batch_local):
        ef_local = jax.tree.map(lambda e: e[0], ef_local)  # strip pod dim
        grads, metrics = _accumulate_grads(
            loss_fn, params, batch_local, microbatches)
        grads, new_ef = _pod_compress(grads, ef_local)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, POD_AXIS), metrics)
        new_ef = jax.tree.map(lambda e: e[None], new_ef)
        return grads, new_ef, metrics

    def step_fn(state: TrainState, batch):
        ef_specs = jax.tree.map(lambda e: P(POD_AXIS), state.ef)
        batch_in = jax.tree.map(lambda x: P(POD_AXIS), batch)
        from repro.utils import shard_map
        grads, new_ef, metrics = shard_map(
            pod_body, mesh=mesh,
            in_specs=(P(), ef_specs, batch_in),
            out_specs=(P(), ef_specs, P()),
            axis_names={POD_AXIS},
        )(state.params, state.ef, batch)
        params, opt, om = apply_updates(state.params, grads, state.opt, ocfg)
        return TrainState(params, opt, state.step + 1, new_ef), \
            {**metrics, **om}

    return step_fn


def make_eval_step(model: Model):
    def eval_fn(params, batch):
        return model.loss_fn(params, batch)[1]
    return eval_fn


def make_prefill_step(model: Model, max_len: int, enc_len: int = 0):
    """(params, batch) -> (last_logits, cache): causal pass writing the cache."""
    def prefill_fn(params, batch):
        b = batch["tokens"].shape[0]
        cache = model.init_cache(b, max_len, enc_len)
        if model.mesh is not None:
            from jax.sharding import NamedSharding
            cache = jax.tree.map(
                lambda x, s: jax.lax.with_sharding_constraint(
                    x, NamedSharding(model.mesh, s)),
                cache, model.cache_specs(b))
        logits, cache, _ = model.forward(
            params, tokens=batch["tokens"], embeds=batch.get("embeds"),
            mode="causal", cache=cache, pos=None)
        return logits[:, -1], cache
    return prefill_fn


def make_decode_step(model: Model):
    """(params, cache, tokens (B,1), pos ()) -> (logits (B,V), cache)."""
    def decode_fn(params, cache, tokens, pos):
        logits, cache = model.decode_step(params, cache, tokens, pos)
        return logits[:, -1], cache
    return decode_fn
