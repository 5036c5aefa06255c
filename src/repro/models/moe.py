"""Mixture-of-Experts layer — expert dispatch IS the paper's shuffle.

The paper's single network operator is hash-partition + AllToAll
(``repro.core.repartition``). MoE token routing is the same operator with
the router's top-k argmax playing the role of the hash: tokens are packed
into equal-capacity per-expert buckets (``pack_by_partition`` — the exact
code path the relational shuffle uses) and exchanged with one
``jax.lax.all_to_all`` over the MODEL axis (expert parallelism), processed,
and shuffled back. This substantiates the paper's "data processing as a
function, everywhere" thesis *inside* the training step (DESIGN.md §2).

Three execution paths:
* ``ep_shuffle`` (default on meshes with model>1): shard_map + explicit
  all_to_all as above. Deterministic collective schedule; the roofline's
  collective term for MoE cells comes from here.
* ``ep_psum`` (decode / S==1): every shard computes its local experts for
  all tokens and contributions are psum-merged — no shuffle for tiny S.
* local (1-device / tests): same packing, no collective.

Capacity semantics mirror the relational shuffle: per-expert buckets are
static; overflow tokens are *dropped and counted* (standard MoE capacity
drop == Cylon's surfaced bucket overflow).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.repartition import pack_by_partition, staged_all_to_all
from repro.core.stats import pick_stages
from repro.models.common import (
    DATA_AXIS, MODEL_AXIS, ModelConfig, ShardingRules)
from repro.models.layers import _dense
from repro.utils import ceil_div, round_up, shard_map


def padded_experts(cfg: ModelConfig, model_size: int) -> int:
    """Experts padded up so the EP axis divides them (qwen2: 60 -> 64)."""
    return round_up(cfg.moe_num_experts, max(model_size, 1))


def init_moe(key, cfg: ModelConfig, rules: ShardingRules):
    d, ff = cfg.d_model, cfg.moe_d_ff
    e_pad = padded_experts(cfg, rules.model)
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense(ks[0], (d, cfg.moe_num_experts), jnp.float32),
        "wi": _dense(ks[1], (e_pad, d, ff), cfg.param_dtype),
        "wg": _dense(ks[2], (e_pad, d, ff), cfg.param_dtype),
        "wo": _dense(ks[3], (e_pad, ff, d), cfg.param_dtype),
    }
    s = {
        "router": P(None, None),
        "wi": rules.expert_col(e_pad, d, ff),
        "wg": rules.expert_col(e_pad, d, ff),
        "wo": rules.expert_row(e_pad, ff, d),
    }
    if cfg.moe_num_shared:
        sh_ff = cfg.moe_num_shared * ff
        p["shared"] = {"wi": _dense(ks[4], (d, sh_ff), cfg.param_dtype),
                       "wg": _dense(jax.random.fold_in(ks[4], 1), (d, sh_ff),
                                    cfg.param_dtype),
                       "wo": _dense(jax.random.fold_in(ks[4], 2), (sh_ff, d),
                                    cfg.param_dtype)}
        s["shared"] = {"wi": rules.col(d, sh_ff), "wg": rules.col(d, sh_ff),
                       "wo": rules.row(sh_ff, d)}
    return p, s


def _route(router_w, xt, cfg: ModelConfig):
    """Token routing: top-k experts + combine weights + load-balance loss."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, cfg.moe_top_k)
    topw = topw / jnp.maximum(jnp.sum(topw, -1, keepdims=True), 1e-9)
    # switch-style load-balance aux: E * sum_e f_e * p_e
    e = cfg.moe_num_experts
    frac_tokens = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(
        1.0 / topi.size)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return topi, topw, aux


def _expert_ffn(wi, wg, wo, toks):
    """(E_loc, C, d) tokens through per-expert SwiGLU."""
    dt = toks.dtype
    h = jnp.einsum("ecd,edf->ecf", toks, wi.astype(dt))
    g = jnp.einsum("ecd,edf->ecf", toks, wg.astype(dt))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo.astype(dt))


def _bucket_capacity(tokens: int, e_pad: int, cfg: ModelConfig) -> int:
    c = ceil_div(int(tokens * cfg.moe_top_k * cfg.moe_capacity_factor), e_pad)
    return max(8, round_up(c, 8))


def _dispatch_compute_combine(p, xt, cfg: ModelConfig, e_pad: int,
                              axis: str | None):
    """Shared body: pack -> (all_to_all) -> expert FFN -> (all_to_all) -> unpack.

    xt: (T, d) local tokens. With `axis`, expert weights are sharded over it
    (E_loc = e_pad / M local experts) and buckets ride one all_to_all each way.
    """
    t, d = xt.shape
    topi, topw, aux = _route(p["router"], xt, cfg)
    k = cfg.moe_top_k
    flat_e = topi.reshape(t * k).astype(jnp.int32)
    cap = _bucket_capacity(t, e_pad, cfg)
    send_idx, hist = pack_by_partition(flat_e, e_pad, cap)  # (E, cap)
    tok_idx = send_idx // k  # row in xt for each slot
    sel = (send_idx >= 0)[..., None]
    buf = jnp.where(sel, xt[jnp.clip(tok_idx, 0, t - 1)], 0)  # (E, cap, d)

    if axis is not None:
        m = jax.lax.axis_size(axis)
        e_loc = e_pad // m
        # (E, cap, d) -> (M, E_loc*cap, d) -> exchange -> (E_loc, M*cap, d)
        sendb = buf.reshape(m, e_loc * cap, d)
        # expert dispatch rides the relational shuffle's staged primitive:
        # same cost-sized pipeline depth, same bit-identity contract
        stages = cfg.moe_shuffle_stages
        if stages is None:
            stages = pick_stages(
                m * m * e_loc * cap * d * sendb.dtype.itemsize, e_loc * cap)
        recv = staged_all_to_all(sendb, axis, stages=stages,
                                 shuffle_mode=cfg.moe_shuffle_mode)
        recv = recv.reshape(m, e_loc, cap, d).transpose(1, 0, 2, 3) \
            .reshape(e_loc, m * cap, d)
        out = _expert_ffn(p["wi"], p["wg"], p["wo"], recv)
        back = out.reshape(e_loc, m, cap, d).transpose(1, 0, 2, 3) \
            .reshape(m, e_loc * cap, d)
        back = staged_all_to_all(back, axis, stages=stages,
                                 shuffle_mode=cfg.moe_shuffle_mode)
        back = back.reshape(e_pad, cap, d)
    else:
        back = _expert_ffn(p["wi"], p["wg"], p["wo"], buf)

    # scatter processed slots to flat (t*k) entries; overflow slots dropped
    flat_dest = jnp.where(send_idx >= 0, send_idx, t * k).reshape(-1)
    out_flat = jnp.zeros((t * k, d), xt.dtype).at[flat_dest].set(
        back.reshape(e_pad * cap, d), mode="drop")
    y = jnp.sum(out_flat.reshape(t, k, d) * topw[..., None].astype(xt.dtype), 1)
    dropped = jnp.sum(jnp.maximum(hist - cap, 0))
    return y, {"moe_aux": aux, "moe_dropped": dropped.astype(jnp.float32)}


def _shuffle_body(p, x, *, cfg: ModelConfig, e_pad: int):
    """shard_map body over MODEL axis: x (B, S_loc, d) seq-sharded."""
    b, s_loc, d = x.shape
    y, aux = _dispatch_compute_combine(
        p, x.reshape(b * s_loc, d), cfg, e_pad, MODEL_AXIS)
    # aux values are per-shard partials -> mean over the axis
    aux = {k: jax.lax.pmean(v, MODEL_AXIS) for k, v in aux.items()}
    return y.reshape(b, s_loc, d), aux


def _psum_body(p_local, x, *, cfg: ModelConfig, e_pad: int, e_loc: int):
    """Decode path: each shard computes only its local experts, psum-merged.

    x (B, S, d) replicated over MODEL; p_local expert weights are the local
    (E_loc, ...) slice; router weight replicated.
    """
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    topi, topw, aux = _route(p_local["router"], xt, cfg)
    k = cfg.moe_top_k
    shard = jax.lax.axis_index(MODEL_AXIS)
    lo = shard * e_loc
    flat_e = topi.reshape(t * k).astype(jnp.int32) - lo
    flat_e = jnp.where((flat_e >= 0) & (flat_e < e_loc), flat_e, -1)
    cap = max(8, round_up(ceil_div(t * k, 1), 8))  # no drops in decode
    send_idx, hist = pack_by_partition(flat_e, e_loc, cap)
    tok_idx = send_idx // k
    sel = (send_idx >= 0)[..., None]
    buf = jnp.where(sel, xt[jnp.clip(tok_idx, 0, t - 1)], 0)
    out = _expert_ffn(p_local["wi"], p_local["wg"], p_local["wo"], buf)
    flat_dest = jnp.where(send_idx >= 0, send_idx, t * k).reshape(-1)
    out_flat = jnp.zeros((t * k, d), xt.dtype).at[flat_dest].set(
        out.reshape(e_loc * cap, d), mode="drop")
    y = jnp.sum(out_flat.reshape(t, k, d) * topw[..., None].astype(xt.dtype), 1)
    y = jax.lax.psum(y, MODEL_AXIS)
    aux = {"moe_aux": aux, "moe_dropped": jnp.float32(0)}
    return y.reshape(b, s, d), aux


def moe_fwd(p, x: jax.Array, cfg: ModelConfig, rules: ShardingRules,
            mesh=None):
    """MoE layer forward. x (B, S, d). Returns (y, aux dict of scalars)."""
    b, s, d = x.shape
    m = mesh.shape.get(MODEL_AXIS, 1) if mesh is not None else 1
    e_pad = padded_experts(cfg, m)
    routed_p = {k: p[k] for k in ("router", "wi", "wg", "wo")}

    if mesh is None or m == 1 or not cfg.ep_shuffle \
            or cfg.layout == "fsdp":
        y, aux = _dispatch_compute_combine(
            routed_p, x.reshape(b * s, d), cfg, e_pad, None)
        y = y.reshape(b, s, d)
    elif s % m == 0 and s >= m:
        batch = rules.batch_axes()
        espec = {"router": P(None, None), "wi": P(MODEL_AXIS, None, None),
                 "wg": P(MODEL_AXIS, None, None),
                 "wo": P(MODEL_AXIS, None, None)}
        y, aux = shard_map(
            partial(_shuffle_body, cfg=cfg, e_pad=e_pad), mesh=mesh,
            in_specs=(espec, P(batch, MODEL_AXIS, None)),
            out_specs=(P(batch, MODEL_AXIS, None), P()),
        )(routed_p, x)
    else:  # decode (S == 1): psum over local-expert contributions
        batch = rules.batch_axes()
        espec = {"router": P(None, None), "wi": P(MODEL_AXIS, None, None),
                 "wg": P(MODEL_AXIS, None, None),
                 "wo": P(MODEL_AXIS, None, None)}
        e_loc = e_pad // m
        y, aux = shard_map(
            partial(_psum_body, cfg=cfg, e_pad=e_pad, e_loc=e_loc), mesh=mesh,
            in_specs=(espec, P(batch, None, None)),
            out_specs=(P(batch, None, None), P()),
        )(routed_p, x)

    if cfg.moe_num_shared:
        from repro.models.layers import mlp_fwd
        y = y + mlp_fwd(p["shared"], x)
    return y, aux
