"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
must set XLA_FLAGS before any jax initialization.

Mesh shapes (TPU v5e pods):
  single-pod:  (data=16, model=16)            — 256 chips
  multi-pod:   (pod=2, data=16, model=16)     — 512 chips, 'pod' is the
               cross-pod (DCN) data-parallel axis; gradient reduction is
               hierarchical (reduce-scatter within pod, all-reduce across).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    # Auto axes: the model code pins activations with
    # with_sharding_constraint, which refuses Explicit axes (make_mesh's
    # default)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """``(data, model)`` mesh over the visible devices, for serving,
    training and tests."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh {data}x{model} needs {data*model} devices, "
                         f"have {n}")
    return _auto_mesh((data, model), ("data", "model"))


# Hardware constants for roofline (TPU v5e per chip)
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # bytes/s
ICI_BW_PER_LINK = 50e9         # bytes/s per direction per link
CHIPS_PER_POD = 256
