"""Compile a cell's programs at its real size for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload q12_sf40_4chip

No chip is needed: the TPU compiler compiles for the devices of a
described ``v5e:2x2`` topology. For the cell's generator, its query's
cost-sized plan (with the statistics ``analyze`` would give at that size)
and, on several chips, the safe-capacity plan that an overflow retry
runs, it prints the bytes per device that ``memory_analysis()`` reports,
whether the compiled text calls a Pallas kernel, and the jitted functions
it calls (``bench.roofline``: the names a ``<kernel>_roofline`` reader
can read). The kernels compile as on the chip, not in interpret mode. A
program that does not fit the chip fails to compile here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from bench import roofline  # noqa: E402
from bench.run import AXIS, load_cell  # noqa: E402

#: distinct values of each column at full scale (None: as many as rows, so
#: the 4096-bucket sketch saturates and reports the row count)
DOMAIN = {"returnflag": 3, "linestatus": 2, "shipmode": 7, "shipinstruct": 4,
          "linenumber": 7, "quantity": 50, "discount": 11, "tax": 9,
          "orderstatus": 3, "orderpriority": 5, "shippriority": 1,
          "o_high": 2, "o_low": 2, "shipdate": 2526, "commitdate": 2466,
          "receiptdate": 2555, "orderdate": 2406}


def _stats(columns: dict, rows_per_chip: int, chips: int):
    from repro.core import stats as ST

    rows = rows_per_chip * chips
    cols = tuple((c, ST.ColumnStats(float(min(DOMAIN.get(c) or rows, rows))))
                 for c in sorted(columns))
    return ST.TableStats(rows=float(rows), columns=cols,
                         max_shard_rows=float(rows_per_chip))


def _report(label: str, compiled):
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes")
    vals = {f: int(getattr(mem, f, 0)) for f in fields}
    total = sum(vals.values())
    calls = sorted({n for ns in roofline.hlo_calls(text).values()
                    for n in ns})
    print(json.dumps({"program": label, "bytes_per_device": total, **vals,
                      "tpu_custom_call": "tpu_custom_call" in text,
                      "all_to_all": "all-to-all" in text,
                      "calls": calls}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    from jax.experimental import topologies

    import repro.utils
    from repro.core import plan as PL
    from repro.core.context import DistContext, DistTable
    from repro.core.frame import LazyFrame
    from repro.core.serving import ServingSession

    jax.config.update("jax_enable_compilation_cache", False)
    # the kernels decide interpret mode by the backend, which is the CPU
    # here: compile them as the chip runs them
    repro.utils.on_tpu = lambda: True
    spec = load_cell(args.workload)
    chips = spec["cell"]["chips"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices[:chips]), (AXIS,),
                axis_types=(AxisType.Auto,))
    shard = NamedSharding(mesh, P(AXIS))
    config = spec["config"]

    # the generator: its output shapes, and its own footprint
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=NamedSharding(mesh, P()))
    fn, rows = spec["generator"].program(config, mesh, AXIS)
    held = {t: tuple(s["columns"]) for t, s in config["tables"].items()}
    _report("generate", fn.lower(key).compile())

    # the query, as the session would plan it over analyzed tables
    ctx = DistContext(mesh=mesh, axis_name=AXIS)
    sess = ServingSession(ctx)
    tabs = {}
    for t, cols in held.items():
        shapes = fn.eval_shape(key)[t]
        columns = {c: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shard)
                   for c, s in shapes.items()}
        rc = jax.ShapeDtypeStruct((chips,), jnp.int32, sharding=shard)
        tabs[t] = DistTable(columns, rc,
                            stats=_stats(columns, rows[t], chips))
    sess._tables.update(tabs)
    frame: LazyFrame = spec["query"].build(sess, spec["mix"]["params"])
    inputs = frame._inputs
    schemas = [t.schema for t in inputs]
    plan, _ = PL.optimize_with_partitioning(
        frame._plan, schemas, chips, input_stats=[t.stats for t in inputs])
    print(PL.explain(plan, schemas, [t.stats for t in inputs]), flush=True)
    variants = [("query (cost-sized)", plan, False)]
    if chips > 1:
        safe, _ = PL.optimize_with_partitioning(frame._plan, schemas, chips)
        variants.append(("query (safe capacity, after an overflow)", safe,
                         True))
    for label, p, safe in variants:
        def run_plan(*tables, p=p, safe=safe):
            return PL.execute_plan(p, tables, axis_name=AXIS,
                                   num_shards=chips, safe_capacity=safe)

        argv_ = tuple((t.columns, t.row_counts) for t in inputs)
        compiled = jax.jit(ctx._make_global(run_plan)).lower(*argv_).compile()
        _report(label, compiled)
    return 0


if __name__ == "__main__":
    sys.exit(main())
