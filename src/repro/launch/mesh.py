"""Production meshes (assignment contract).

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): single-pod (16, 16) = 256 chips ("data", "model");
multi-pod (2, 16, 16) = 512 chips ("pod", "data", "model"). The pod axis
rides DCN; data/model ride ICI — transport selection by axis choice
(core/context.py docstring).
"""
from __future__ import annotations

import jax

from repro.utils import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1, pod: int = 1):
    """Development mesh over whatever devices exist (tests/examples)."""
    n = jax.device_count()
    data = n // (model * pod)
    assert data * model * pod == n, (n, model, pod)
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
