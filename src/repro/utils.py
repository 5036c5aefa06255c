"""Small shared utilities: padding, pow2 math, platform detection."""
from __future__ import annotations

import functools
import os
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding


@functools.cache
def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Pallas kernels run in interpret mode off-TPU (this container is CPU)."""
    return not on_tpu()


#: the persistent compile cache's fixed home inside the checkout (gitignored);
#: a fixed path, because the path is part of the cache key
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep compiled programs across processes. Where the environment sets
    ``JAX_COMPILATION_CACHE_DIR``, JAX reads it itself and nothing here
    changes; otherwise the cache goes to :data:`COMPILE_CACHE_DIR`."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))


def next_pow2(n: int) -> int:
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(n: int, m: int) -> int:
    return ceil_div(n, m) * m


def pad_to(x: jax.Array, n: int, fill) -> jax.Array:
    """Pad 1-D array x up to length n with `fill` (no-op if already n)."""
    if x.shape[0] == n:
        return x
    assert x.shape[0] < n, (x.shape, n)
    return jnp.concatenate(
        [x, jnp.full((n - x.shape[0],) + x.shape[1:], fill, dtype=x.dtype)]
    )


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with every axis ``Auto``: arrays are placed by the
    compiler, so plain gathers and scatters on sharded arrays stay legal
    (the default ``Explicit`` axes type each array by its sharding and
    reject them)."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names))


def safe_constrain(x, mesh, spec):
    """with_sharding_constraint that no-ops inside manual (shard_map)
    regions, where the full-mesh NamedSharding is rejected — e.g. the
    pod-compressed gradient path wraps the whole model in a pod-manual
    shard_map; the inner TP constraints become hints we can drop there."""
    if AxisType.Manual in jax.sharding.get_abstract_mesh().axis_types:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    except ValueError:
        return x


def shard_map(f, **kw):
    """``jax.shard_map`` without the varying-manual-axes check: the engine's
    per-shard bodies mix shard-local and replicated values freely."""
    return jax.shard_map(f, check_vma=False, **kw)


def tree_bytes(tree) -> int:
    return sum(
        x.size * x.dtype.itemsize
        for x in jax.tree.leaves(tree)
        if hasattr(x, "dtype")
    )
