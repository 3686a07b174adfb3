"""Grouped matrix multiplication for routed experts (Pallas TPU).

Rows of ``lhs`` [m, k] are sorted by expert; ``group_sizes`` [E] counts
each expert's rows, and rows past their sum belong to no expert.  Row r of
group e is multiplied by expert e's matrix [k, n] of layer ``layer`` in the
stacked weights ``rhs`` [L, E, k, n], read where they lie: a decode step
that scans its layers passes the whole stack and the layer index (a scalar
prefetch), so no layer's experts are sliced out first.  Slicing them out
for a kernel's operand costs a copy of every expert matrix of the layer.

The schedule is Megablox's (``jax.experimental.pallas.ops.tpu.megablox``,
whose ``make_group_metadata`` lays it out): grid (n tiles, group tiles,
k tiles); a row tile that holds rows of several groups is visited once per
group, consecutively, and each visit stores only its group's rows.  So an
expert's weight tile is fetched once for every row tile its rows touch: a
decode step's 512 routed rows read each hit expert's weights about once.
The kernel runs under a jit of its own name, ``expert_gmm``, which the
compiled program gives the custom call: the device trace finds it by that.

Tiles: row tiles of 128 for decode-sized problems and of 512 for
prefill-sized ones (four times the MXU work per weight fetch, which keeps
prefill compute-bound); k and n tiles as large as the scoped VMEM budget
takes, the whole dimension or a multiple of 128 that divides it.  Output
rows outside every group are left unwritten: callers mask them.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the module: the package's own ``gmm`` attribute is the differentiable op
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

_VMEM_BUDGET = 12 * 2**20      # under v5e's 16 MiB scoped VMEM default


def _block_options(dim: int) -> list[int]:
    """Block sizes Mosaic tiles a dimension by: the whole of it, or a
    multiple of 128 that divides it."""
    opts = {dim}
    opts.update(b for b in range(128, dim, 128) if dim % b == 0)
    return sorted(opts, reverse=True)


def tiling(m: int, k: int, n: int, itemsize: int = 2) -> tuple[int, int, int]:
    """(tm, tk, tn) for an [m, k] x [k, n] group product: double-buffered
    lhs, rhs and f32 out blocks plus the f32 accumulator within budget."""
    tm = 512 if m >= 4096 else 128

    def vmem(tk, tn):
        return (2 * tm * tk * itemsize + 2 * tk * tn * itemsize
                + 3 * tm * tn * 4)
    fits = [(tk, tn) for tk in _block_options(k) for tn in _block_options(n)
            if vmem(tk, tn) <= _VMEM_BUDGET]
    if not fits:
        return tm, min(_block_options(k)), min(_block_options(n))
    return (tm,) + max(fits, key=lambda t: (t[0] * t[1], t[1]))


def _kernel(offsets, group_ids, m_tile_ids, layer, lhs_ref, rhs_ref, out_ref,
            acc_ref, *, tm: int, tn: int, tiles_k: int):
    del layer                                      # used by the index maps
    grid_id = pl.program_id(1)
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        group = group_ids[grid_id]
        rows = (jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
                + m_tile_ids[grid_id] * tm)
        mine = (rows >= offsets[group]) & (rows < offsets[group + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...], out_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
               layer: jax.Array, *, interpret: bool = False) -> jax.Array:
    """lhs [m, k], rhs [L, E, k, n], group_sizes [E] i32, layer: scalar
    i32 -> f32 [m, n], each group's rows times its expert of that layer."""
    m, k = lhs.shape
    E, n = rhs.shape[1], rhs.shape[3]
    tm, tk, tn = tiling(m, k, n, jnp.dtype(lhs.dtype).itemsize)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    (offsets, group_ids, m_tile_ids), tiles = _megablox.make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m + pad, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=E,
        visit_empty_groups=False)
    tiles_k, tiles_n = k // tk, n // tn

    def lhs_map(ni, gi, ki, offsets, group_ids, m_tile_ids, layer):
        return m_tile_ids[gi], ki

    def rhs_map(ni, gi, ki, offsets, group_ids, m_tile_ids, layer):
        return layer[0], group_ids[gi], ki, ni

    def out_map(ni, gi, ki, offsets, group_ids, m_tile_ids, layer):
        return m_tile_ids[gi], ni

    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((m + pad, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles_n, tiles, tiles_k),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, None, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(offsets, group_ids, m_tile_ids,
      jnp.reshape(layer, (1,)).astype(jnp.int32), lhs, rhs)
    return out[:m] if pad else out
