import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Perf hillclimb driver: lower a (arch, shape) cell under config variants
and report the three roofline terms per variant (EXPERIMENTS.md §Perf).

    PYTHONPATH=src python -m repro.launch.hillclimb --cell llama3_train \
        --out results/perf_llama3.json
"""
import argparse
import json

import jax

from repro.configs import get_config
from repro.launch.dryrun import compile_cell, roofline_cell
from repro.launch.mesh import make_production_mesh
from repro.roofline import analysis as RA
from repro.utils import use_compile_cache


def _measure(cfg, shape, mesh, *, microbatches=None):
    rec = roofline_cell(cfg, shape, mesh)
    try:
        lowered, compiled = compile_cell(cfg, shape, mesh,
                                         microbatches=microbatches)
        mem = RA.memory_stats(compiled)
        up = RA.cpu_upcast_temp_bytes(compiled.as_text())
        mem["peak_adjusted"] = max(mem["peak_bytes"] - up["total"]
                                   + up["largest"], mem["argument_bytes"])
        rec["memory"] = mem
    except Exception as e:  # noqa: BLE001
        rec["memory"] = {"error": str(e)[:300]}
    return rec


# --- variant sets per chosen cell -------------------------------------------

def cell_llama3_train(mesh):
    base = get_config("llama3-8b")
    return "llama3-8b", "train_4k", [
        ("baseline_tp16", base),
        ("fsdp_layout", base.replace(layout="fsdp")),
        ("fsdp_layout_remat_dots", base.replace(layout="fsdp", remat="dots")),
        ("tp16_remat_dots", base.replace(remat="dots")),
    ]


def cell_minicpm3_decode(mesh):
    base = get_config("minicpm3-4b")
    return "minicpm3-4b", "decode_32k", [
        ("baseline_latent_cache", base),
        ("latent_seqshard", base.replace(mla_seq_shard=True)),
    ]


def cell_qwen2_train(mesh):
    base = get_config("qwen2-moe-a2.7b")
    return "qwen2-moe-a2.7b", "train_4k", [
        ("baseline_ep_shuffle", base),
        ("gspmd_gathered_experts", base.replace(ep_shuffle=False)),
        ("ep_shuffle_cf1.0", base.replace(moe_capacity_factor=1.0)),
        ("ep_shuffle_cf2.0", base.replace(moe_capacity_factor=2.0)),
    ]


CELLS = {
    "llama3_train": cell_llama3_train,
    "minicpm3_decode": cell_minicpm3_decode,
    "qwen2_train": cell_qwen2_train,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, choices=list(CELLS))
    ap.add_argument("--out", required=True)
    ap.add_argument("--variants", default=None,
                    help="comma-separated subset of variant names")
    args = ap.parse_args()
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 5)

    mesh = make_production_mesh()
    arch, shape, variants = CELLS[args.cell](mesh)
    want = set(args.variants.split(",")) if args.variants else None
    out = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            out = json.load(f)
    for name, cfg in variants:
        if want and name not in want:
            continue
        print(f"[variant] {name}")
        try:
            rec = _measure(cfg, shape, mesh)
            t, tf = rec["terms"], rec["terms_flash"]
            print(f"  compute {t['compute_s']*1e3:.1f}ms | mem(fl) "
                  f"{tf['memory_s']*1e3:.1f}ms | coll "
                  f"{t['collective_s']*1e3:.1f}ms -> {tf['dominant']}"
                  f" | peak {rec['memory'].get('peak_adjusted', 0)/2**30:.1f}"
                  " GiB")
        except Exception as e:  # noqa: BLE001
            rec = {"error": f"{type(e).__name__}: {e}"}
            print(f"  FAIL: {e}")
        out.setdefault(arch, {}).setdefault(shape, {})[name] = rec
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1, default=float)
    print("[done]", args.out)


if __name__ == "__main__":
    main()
