"""The ``(data, model)`` mesh that serving, training and tests build.

``make_host_mesh`` is a FUNCTION (not a module constant) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    # Auto axes: the model code pins activations with
    # with_sharding_constraint, which refuses Explicit axes (make_mesh's
    # default)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """``(data, model)`` mesh over the visible devices, for serving,
    training and tests."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh {data}x{model} needs {data*model} devices, "
                         f"have {n}")
    return _auto_mesh((data, model), ("data", "model"))
