"""Flash-decoding — single-token attention over a long KV cache (Pallas TPU).

One new token per sequence attends to a cache of S past positions.  The
arithmetic intensity is O(1) FLOP/byte (every cache byte is read once), so
the kernel is engineered for HBM streaming, not MXU:

* grid (B, KH, S/bs) — innermost dim walks the cache sequentially while
  (acc, m, l) for all G q-heads of this kv-head ride in VMEM scratch
  (split-K flash-decoding, recurrence via sequential grid);
* the per-sequence valid length arrives via scalar prefetch (SMEM) and
  masks the tail block — no host-side padding logic;
* q is pre-reshaped [B, KH, G, Dh] so one grid step consumes a [G, Dh]
  q-tile and a [bs, Dh] cache tile, emitting [G, bs] scores on the MXU;
* the cache is viewed as [B, S, KH·Dh] (a free reshape) and each kv-head is
  a lane slice of it, so a cache tile's last two dims are (bs, Dh) — the
  (8, 128) tiling Mosaic demands — and the cache is never copied.  A block
  of one head from [B, S, KH, Dh] would put that head second-minor, which
  the TPU compiler refuses.  Head dims that are not a lane multiple (with
  KH > 1) are zero-padded to one, which costs a copy of the cache.

Layouts: q [B, KH, G, Dh]; k/v cache [B, S, KH, Dh]; lens [B] i32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e30


def _kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, block_s: int):
    b = pl.program_id(0)
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = lens_ref[b]
    s_start = si * block_s

    @pl.when(s_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [G, Dh]
        k = k_ref[0].astype(jnp.float32)                     # [bs, Dh]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [G,bs]
        pos = s_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos >= length, NEG_INF, s)
        m_prev = m_ref[...]                                  # [G, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lens: jax.Array, *, block_s: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: [B, H, Dh]; caches [B, S, KH, Dh]; lens [B] -> out [B, H, Dh]."""
    B, H, Dh = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    scale = 1.0 / math.sqrt(Dh)
    block_s = min(block_s, S)
    ns = pl.cdiv(S, block_s)
    Dp = Dh if KH == 1 else -(-Dh // 128) * 128   # lane-aligned head dim
    pad_s = ns * block_s - S
    if pad_s or Dp != Dh:
        widths = ((0, 0), (0, pad_s), (0, 0), (0, Dp - Dh))
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Dp - Dh)))
    k_cache = k_cache.reshape(B, ns * block_s, KH * Dp)
    v_cache = v_cache.reshape(B, ns * block_s, KH * Dp)
    qg = q.reshape(B, KH, G, Dp)
    lens = lens.astype(jnp.int32)

    kernel = functools.partial(_kernel, scale=scale, block_s=block_s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KH, ns),
        in_specs=[
            pl.BlockSpec((1, 1, G, Dp), lambda b, kh, si, lens: (b, kh, 0, 0)),
            pl.BlockSpec((1, block_s, Dp),
                         lambda b, kh, si, lens: (b, si, kh)),
            pl.BlockSpec((1, block_s, Dp),
                         lambda b, kh, si, lens: (b, si, kh)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dp),
                               lambda b, kh, si, lens: (b, kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, Dp), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, Dp), q.dtype),
        interpret=interpret,
    )(lens, qg, k_cache, v_cache)
    return out[..., :Dh].reshape(B, H, Dh)
