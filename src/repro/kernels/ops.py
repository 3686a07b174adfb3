"""jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in interpret mode — the kernel
body runs in Python per grid step, bit-accurate to the TPU lowering's
semantics.  On TPU they compile to Mosaic.  `interpret=None` auto-detects.
"""
from __future__ import annotations

import jax

from . import decode_attention as _da
from . import flash_attention as _fa
from . import grouped_matmul as _gm
from . import rmsnorm as _rn
from . import ssd_scan as _ss


def _auto(interpret: bool | None) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=_auto(interpret))


def decode_attention(q, k_cache, v_cache, lens, *, block_s: int = 256,
                     interpret: bool | None = None):
    return _da.decode_attention(q, k_cache, v_cache, lens, block_s=block_s,
                                interpret=_auto(interpret))


def decode_attention_stacked(q, k_all, v_all, lens, layer, *,
                             block_s: int = 256,
                             interpret: bool | None = None):
    return _da.decode_attention_stacked(q, k_all, v_all, lens, layer,
                                        block_s=block_s,
                                        interpret=_auto(interpret))


stacked_fits = _da.stacked_fits


def expert_gmm(lhs, rhs, group_sizes, layer, *,
               interpret: bool | None = None):
    return _gm.expert_gmm(lhs, rhs, group_sizes, layer,
                          interpret=_auto(interpret))


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128, block_h: int = 8,
             interpret: bool | None = None):
    return _ss.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, block_h=block_h,
                        interpret=_auto(interpret))


def rmsnorm(x, w, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool | None = None):
    return _rn.rmsnorm(x, w, eps=eps, block_rows=block_rows,
                       interpret=_auto(interpret))


def jit_chain(stages):
    """Compose stream-combinator stages into ONE jitted program.

    ``stages`` is a sequence of ``(kind, fn)`` where ``kind`` is ``"map"``
    (``fn(payload) -> payload``) or ``"filter"`` (``fn(payload) -> bool``).
    Returns a jitted ``program(payload) -> (payload, keep)``: interior hops
    become in-program values (no bus traffic, no per-hop dispatch), and filter
    predicates are *predicated* — every stage runs, the combined keep flag
    decides on the host whether the exit message is emitted.  This is the
    device executor behind the chain-fusion pass (core/fusion.py).
    """
    import jax.numpy as jnp

    def program(payload):
        keep = jnp.asarray(True)
        for kind, fn in stages:
            if kind == "filter":
                keep = jnp.logical_and(keep, jnp.asarray(fn(payload)))
            else:
                payload = fn(payload)
        return payload, keep

    return jax.jit(program)


def jit_chain_batched(stages):
    """Batched variant of :func:`jit_chain`: ONE vmapped + jitted program over
    a whole burst of payloads.

    Input is the per-message payload dict with every field stacked along a
    leading batch dimension; output is ``(stacked_payload, keep_mask)`` where
    ``keep_mask`` is a ``(N,)`` bool of per-message predicated-filter
    decisions.  Per-message semantics are exactly ``jit_chain``'s — the vmap
    axis only amortizes the per-message XLA dispatch + host<->device sync
    that dominates short chains under load into one device call per burst.
    """
    import jax.numpy as jnp

    def single(payload):
        keep = jnp.asarray(True)
        for kind, fn in stages:
            if kind == "filter":
                keep = jnp.logical_and(keep, jnp.asarray(fn(payload)))
            else:
                payload = fn(payload)
        return payload, keep

    return jax.jit(jax.vmap(single))


def jit_chain_sharded(stages, mesh, specs=None, stats=None):
    """Mesh-partitioned variant of :func:`jit_chain_batched`.

    Same contract — ``program(stacked_payload) -> (stacked_payload,
    keep_mask)`` with a leading burst dimension — but every input field is
    first committed to a ``NamedSharding`` over ``mesh``: the leading burst
    dim splits across the mesh's first axis by default, and ``specs`` (a
    field-name -> PartitionSpec mapping, as produced by
    :func:`repro.distributed.sharding.burst_spec` from the stream schema's
    sharding hints) overrides per field.  jit then compiles ONE SPMD
    program per batch shape — each device traces its slice of the burst,
    XLA propagates output shardings — so the same vmapped chain that
    amortizes dispatch on one device scales across all visible devices.
    Per-row results are bit-identical to :func:`jit_chain_batched` (vmap
    rows are independent; partitioning only changes which device computes
    a row).  The caller guarantees the leading dim divides the mesh's data
    axis — indivisible bursts must stay on the single-device program.
    With ``stats`` (a fused unit's counter dict), the placement adds its
    seconds to ``stats["place_s"]``.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from ..core.spans import span

    batched = jit_chain_batched(stages)
    specs = dict(specs or {})
    default = PartitionSpec(mesh.axis_names[0])

    def program(payload):
        with span("datax.fused.place", stats, "place_s"):
            placed = {
                k: jax.device_put(v, NamedSharding(mesh,
                                                   specs.get(k, default)))
                for k, v in payload.items()}
        return batched(placed)

    return program
