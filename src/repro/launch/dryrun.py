import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (assignment contract).

For every (architecture × input shape × mesh) cell:

    with mesh:
        lowered = jax.jit(step, in_shardings=..., out_shardings=...) \\
            .lower(**input_specs(arch))
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline

Meshes: single-pod (16,16) and multi-pod (2,16,16) — the multi-pod pass
proves the "pod" axis shards. Additionally (single-pod only) the roofline
extractor lowers depth pairs unrolled (see roofline/analysis.py) and
derives the three roofline terms. Results land in a JSON file consumed by
EXPERIMENTS.md.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k [--mesh single|multi|both] [--roofline] \
        [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, train_microbatches
from repro.configs.shapes import SHAPES, cache_shape, input_specs, runnable
from repro.launch.mesh import make_production_mesh
from repro.models.factory import build_model
from repro.roofline import analysis as RA
from repro.train.optimizer import OptConfig
from repro.train import steps as ST
from repro.utils import use_compile_cache


def _named(mesh, spec_tree, shape_tree):
    """ShapeDtypeStructs carrying NamedShardings (zero-allocation args)."""
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        shape_tree, spec_tree)


def _batch_sds(model, cfg, shape_name, mesh):
    specs = input_specs(cfg, shape_name)
    part = ST.batch_specs(model, specs)
    return _named(mesh, part, specs)


def build_cell(cfg, shape_name: str, mesh, *, microbatches: int | None = None):
    """Returns (step_fn, args tuple of sharded ShapeDtypeStructs)."""
    cell = SHAPES[shape_name]
    model = build_model(cfg, mesh)
    if cell.kind == "train":
        mb = microbatches if microbatches is not None \
            else train_microbatches(cfg.arch)
        dp = model.rules.pod * model.rules.data
        if model.rules.layout == "fsdp":
            dp *= model.rules.model  # model axis is a batch axis here
        mb = max(1, min(mb, cell.global_batch // max(dp, 1)))
        step = ST.make_train_step(model, OptConfig(), microbatches=mb)
        state_shapes = jax.eval_shape(
            lambda k: ST.init_train_state(model, k), jax.random.PRNGKey(0))
        state_sds = _named(mesh, ST.train_state_specs(model), state_shapes)
        return step, (state_sds, _batch_sds(model, cfg, shape_name, mesh))
    if cell.kind == "prefill":
        step = ST.make_prefill_step(model, cell.seq_len, enc_len=cell.seq_len)
        params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        params_sds = _named(mesh, model.param_specs, params_shapes)
        return step, (params_sds, _batch_sds(model, cfg, shape_name, mesh))
    # decode: unroll the layer loop — scan xs->ys caches cannot buffer-alias,
    # doubling KV memory; unrolled DUS aliases in place (serving practice)
    cfg = cfg.replace(scan_layers=False)
    model = build_model(cfg, mesh)
    step = ST.make_decode_step(model)
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sds = _named(mesh, model.param_specs, params_shapes)
    b, s = cache_shape(cfg, shape_name)
    cache_shapes = jax.eval_shape(
        lambda: model.init_cache(b, s, s if cfg.family == "audio" else 0))
    cache_sds = _named(mesh, model.cache_specs(b), cache_shapes)
    dp, _ = model.rules.decode_layout(b)
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32,
                               sharding=NamedSharding(mesh, P(dp, None)))
    pos = jax.ShapeDtypeStruct((), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    return step, (params_sds, cache_sds, tok, pos)


def compile_cell(cfg, shape_name, mesh, *, microbatches=None, donate=True):
    step, args = build_cell(cfg, shape_name, mesh, microbatches=microbatches)
    kw = {}
    if donate and SHAPES[shape_name].kind == "train":
        kw["donate_argnums"] = (0,)
        kw["out_shardings"] = (
            jax.tree.map(lambda x: x.sharding, args[0]), None)
    elif donate and SHAPES[shape_name].kind == "decode":
        # pin the output cache to the input cache's sharding so donation
        # aliases (otherwise in+out caches both stay live — 2x KV memory)
        kw["donate_argnums"] = (1,)
        kw["out_shardings"] = (
            None, jax.tree.map(lambda x: x.sharding, args[1]))
    with mesh:
        lowered = jax.jit(step, **kw).lower(*args)
        compiled = lowered.compile()
    return lowered, compiled


# ---------------------------------------------------------------------------
# roofline extraction (single-pod)
# ---------------------------------------------------------------------------


def _depth_pairs(cfg):
    """[(label, depth-config-fn, depths (l1, l2), weight-at-full-depth)]."""
    if cfg.family == "hybrid":
        per = cfg.attn_every
        periods = cfg.num_layers // per
        rem = cfg.num_layers - periods * per
        return [("period", (per, 2 * per), periods),
                ("rem", (1, 2), rem)]
    if cfg.family == "ssm":
        per = cfg.slstm_every
        periods = cfg.num_layers // per
        rem = cfg.num_layers - periods * per
        pairs = [("period", (per, 2 * per), periods)]
        if rem:
            pairs.append(("rem", (1, 2), rem))
        return pairs
    return [("layer", (1, 2), cfg.num_layers)]


def _cost_of(cfg, shape_name, mesh, depth, *, microbatches):
    c = cfg.replace(num_layers=depth, scan_layers=False, time_unroll=True,
                    remat="none")
    if cfg.family == "audio":
        c = c.replace(encoder_layers=depth)
    lowered, compiled = compile_cell(c, shape_name, mesh,
                                     microbatches=microbatches, donate=False)
    cost = RA.cost_stats(compiled)
    txt = compiled.as_text()
    coll = RA.collective_stats(txt)
    hb = RA.hbm_bytes(txt)
    cost["bytes_xla"] = cost["bytes"]          # raw CPU-backend number
    cost["bytes"] = float(hb["bytes"])         # TPU-traffic model
    cost["bytes_flash"] = float(hb["flash_adjusted"])  # w/ Pallas flash attn
    cost["coll_bytes"] = float(coll["bytes"])
    cost["coll_wire_bytes"] = float(coll["wire_bytes"])
    return cost, coll


def roofline_cell(cfg, shape_name, mesh) -> dict:
    """Three-term roofline via depth-pair extrapolation (DESIGN.md §5)."""
    cell = SHAPES[shape_name]
    # roofline lowers one microbatch (mb=1): same math, small graphs
    total = {}
    detail = {}
    for label, (l1, l2), weight in _depth_pairs(cfg):
        if weight == 0:
            continue
        c1, coll1 = _cost_of(cfg, shape_name, mesh, l1, microbatches=1)
        c2, coll2 = _cost_of(cfg, shape_name, mesh, l2, microbatches=1)
        pair = RA.DepthPair(l1, l2, c1, c2)
        per = pair.per_layer()
        if not total:  # depth-independent part (embed/head/opt) counted once
            base = pair.at(0)
            for k, v in base.items():
                total[k] = total.get(k, 0.0) + v
        for k, v in per.items():
            total[k] = total.get(k, 0.0) + v * weight
        detail[label] = {"per_unit": per, "count": weight,
                         "coll_counts": coll2["counts"]}
    chips = int(np.prod(list(mesh.shape.values())))
    terms = RA.roofline_terms(total["flops"], total["bytes"],
                              total["coll_wire_bytes"])
    terms_flash = RA.roofline_terms(total["flops"], total["bytes_flash"],
                                    total["coll_wire_bytes"])
    model = build_model(cfg, mesh)
    pc = RA.count_params(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    mf = RA.model_flops(cfg, pc, cell.kind, cell.global_batch, cell.seq_len)
    hlo_global_flops = total["flops"] * chips
    return {
        "per_device": total,
        "terms": terms,
        "terms_flash": terms_flash,
        "chips": chips,
        "model_flops": mf,
        "useful_ratio": mf / max(hlo_global_flops, 1.0),
        "params": pc,
        "detail": detail,
    }


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, meshes: list[str], *,
             do_roofline: bool, out: dict):
    cfg = get_config(arch)
    ok, reason = runnable(cfg, shape_name)
    rec = out.setdefault(arch, {}).setdefault(shape_name, {})
    if not ok:
        rec["skipped"] = reason
        print(f"[skip] {arch} x {shape_name}: {reason}")
        return
    for mesh_kind in meshes:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        t0 = time.perf_counter()
        try:
            lowered, compiled = compile_cell(cfg, shape_name, mesh)
            mem = RA.memory_stats(compiled)
            txt = compiled.as_text()
            coll = RA.collective_stats(txt)
            cost = RA.cost_stats(compiled)
            up = RA.cpu_upcast_temp_bytes(txt)
            mem["peak_adjusted"] = max(
                mem["peak_bytes"] - up["total"] + up["largest"],
                mem["argument_bytes"])
            dt = time.perf_counter() - t0
            rec[mesh_kind] = {
                "ok": True, "compile_s": dt, "memory": mem,
                "collectives_once": coll, "cost_once": cost,
                "hbm_frac": mem["peak_adjusted"] / RA.HBM_PER_CHIP,
            }
            print(f"[ok] {arch} x {shape_name} x {mesh_kind}: "
                  f"peak {mem['peak_bytes']/2**30:.2f} GiB/dev raw, "
                  f"{mem['peak_adjusted']/2**30:.2f} GiB TPU-adj "
                  f"({100*rec[mesh_kind]['hbm_frac']:.0f}% HBM), "
                  f"compile {dt:.0f}s")
        except Exception as e:  # noqa: BLE001 — record and continue
            rec[mesh_kind] = {"ok": False, "error": f"{type(e).__name__}: {e}",
                              "traceback": traceback.format_exc()[-2000:]}
            print(f"[FAIL] {arch} x {shape_name} x {mesh_kind}: {e}")
        if do_roofline and mesh_kind == "single" and rec[mesh_kind].get("ok"):
            try:
                t0 = time.perf_counter()
                rec["roofline"] = roofline_cell(cfg, shape_name, mesh)
                rec["roofline"]["extract_s"] = time.perf_counter() - t0
                t = rec["roofline"]["terms"]
                print(f"     roofline: compute {t['compute_s']*1e3:.2f}ms "
                      f"memory {t['memory_s']*1e3:.2f}ms "
                      f"collective {t['collective_s']*1e3:.2f}ms "
                      f"-> {t['dominant']}-bound; "
                      f"useful {100*rec['roofline']['useful_ratio']:.0f}%")
            except Exception as e:  # noqa: BLE001
                rec["roofline"] = {"error": f"{type(e).__name__}: {e}",
                                   "traceback": traceback.format_exc()[-2000:]}
                print(f"[FAIL roofline] {arch} x {shape_name}: {e}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--roofline", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--merge", action="store_true",
                    help="merge into existing --out instead of overwriting")
    args = ap.parse_args()

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 5)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    out: dict = {}
    if args.merge and os.path.exists(args.out):
        with open(args.out) as f:
            out = json.load(f)

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    for arch, shape_name in cells:
        run_cell(arch, shape_name, meshes, do_roofline=args.roofline, out=out)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1, default=float)
    print(f"[done] wrote {args.out}")


if __name__ == "__main__":
    main()
