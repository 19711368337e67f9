"""Production mesh construction (assignment-mandated shapes).

A FUNCTION, not a module constant, so importing never touches jax device
state.  Single-pod: 16x16 = 256 chips (data, model).  Multi-pod: 2x16x16 =
512 chips (pod, data, model) — the 'pod' axis is the slow inter-pod (DCN)
domain; sharding rules place only DP/FSDP traffic on it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the LM stack places activations
    with ``with_sharding_constraint`` and leaves the rest to the compiler,
    which ``jax.make_mesh``'s default ``Explicit`` axes do not allow."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over the real local devices (tests / CPU examples)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))
