"""Activation sharding constraints — logical-axis pins inside model code.

GSPMD propagation from parameter/input shardings alone is not enough at this
scale: observed failure on the (16,16) mesh was attention score tensors with
the *batch dim replicated* (propagation preferred head sharding and dropped
the data axis), inflating per-chip temps ~16×.  Production frameworks
(MaxText, EasyLM) pin activations explicitly; we do the same with logical
names resolved against the active mesh.

Model code calls ``constrain(x, 'batch', 'seq', 'heads', None)`` — a no-op
outside a jit built by repro.train.steps (tests/smoke run unconstrained on
one device).  The jit builders install the context:

    with activation_mesh(mesh, run):
        ... trace ...

Logical axes:
  batch    -> as many DP axes ('pod','data') as divide the dim
  seq      -> run.seq_axis (None by default; 'model' enables sequence/context
              parallelism for long-context cells)
  heads / kv_heads / hidden / channels / vocab -> 'model' when divisible
  expert   -> run.expert_axis (None = experts replicated / TP-sharded inside)
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_TLS = threading.local()


def _axis_size(mesh: Mesh, name) -> int:
    n = 1
    for a in (name if isinstance(name, tuple) else (name,)):
        if a is not None and a in mesh.shape:
            n *= mesh.shape[a]
    return n


@contextlib.contextmanager
def activation_mesh(mesh: Mesh, *, seq_axis=None, expert_axis=None):
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = {"mesh": mesh, "seq_axis": seq_axis, "expert_axis": expert_axis}
    try:
        yield
    finally:
        _TLS.ctx = prev


def current_mesh() -> Mesh | None:
    ctx = getattr(_TLS, "ctx", None)
    return ctx["mesh"] if ctx else None


def whole_on_one_tpu() -> bool:
    """Whether the program being traced runs on one TPU that holds every
    array whole: no mesh and a TPU backend, or a mesh of one TPU device.
    Pallas kernels that read an array where it lies serve only there."""
    mesh = current_mesh()
    if mesh is None:
        return jax.default_backend() == "tpu"
    return mesh.size == 1 and mesh.devices.flat[0].platform == "tpu"


def _resolve(logical, dim: int, ctx) -> object:
    mesh = ctx["mesh"]
    if logical is None:
        return None
    if logical == "batch":
        axes = []
        prod = 1
        pool = ("pod", "data") if "pod" in mesh.shape else ("data",)
        for a in pool:
            if dim % (prod * mesh.shape[a]) == 0:
                axes.append(a)
                prod *= mesh.shape[a]
        return tuple(axes) if axes else None
    if logical == "seq":
        a = ctx["seq_axis"]
        return a if (a and dim % _axis_size(mesh, a) == 0) else None
    if logical == "expert":
        a = ctx["expert_axis"]
        return a if (a and dim % _axis_size(mesh, a) == 0) else None
    if logical in ("heads", "kv_heads", "hidden", "channels", "vocab",
                   "model"):
        return "model" if dim % mesh.shape.get("model", 1) == 0 else None
    raise ValueError(f"unknown logical axis {logical!r}")


def constrain(x: jax.Array, *logical) -> jax.Array:
    """Pin x's sharding by logical dim names; no-op without a context.

    If two dims resolve to the same mesh axis (e.g. seq-parallel 'seq' and
    'heads' both wanting 'model'), the FIRST keeps it — a PartitionSpec may
    not repeat an axis."""
    ctx = getattr(_TLS, "ctx", None)
    if ctx is None:
        return x
    assert len(logical) == x.ndim, (logical, x.shape)
    entries = []
    used: set = set()
    for l, d in zip(logical, x.shape):
        e = _resolve(l, d, ctx)
        flat = e if isinstance(e, tuple) else (e,) if e else ()
        if any(a in used for a in flat):
            e = None
        used.update(flat)
        entries.append(e)
    spec = P(*entries)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx["mesh"], spec))
