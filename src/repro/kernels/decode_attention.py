"""Flash-decoding — single-token attention over a long KV cache (Pallas TPU).

One new token per sequence attends to a cache of S past positions.  The
arithmetic intensity is O(1) FLOP/byte (every cache byte is read once), so
the kernel is engineered for HBM streaming, not MXU:

* grid (B, S/bs) — innermost dim walks the cache sequentially while
  (acc, m, l) for every q-head of this sequence ride in VMEM scratch
  (split-K flash-decoding, recurrence via sequential grid);
* the per-sequence valid length arrives via scalar prefetch (SMEM) and
  masks the tail block — no host-side padding logic.  Blocks wholly past
  the length map to the last live block, so the pipeline fetches nothing
  new for them: a step reads each sequence's live KV only;
* a cache tile is [bs, KH, Dh], every kv-head of bs positions: its last two
  dims are the array's own, which Mosaic's tiling always accepts, so the
  cache is read where it lies and never copied or padded;
* the tile meets all H q-heads in one MXU product, [bs·KH, Dh] × [H, Dh]ᵀ,
  and a mask keeps each q-head's scores against its own kv-head; p·v is
  one product the other way.  That spends KH times the (tiny) MXU work of
  per-head products but reads no head out of the tile: a strided read per
  kv-head took 3.32 ms against 1.75 a qwen3-14b decode step (8 layers, 32
  slots) on a TPU v5e;
* the stacked [L, B, S, KH, Dh] cache a decode step carries is read at the
  layer given by a second scalar prefetch, so no layer is sliced out first.

Layouts: q [B, H, Dh]; k/v cache [B, S, KH, Dh] (`decode_attention`) or
[L, B, S, KH, Dh] with a layer index (`decode_attention_stacked`); lens [B].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e30


def _kernel(lens_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
            l_ref, *, scale: float, block_s: int):
    del layer_ref                                  # used by the index maps
    b = pl.program_id(0)
    si = pl.program_id(1)
    ns = pl.num_programs(1)
    bs, KH, Dh = k_ref.shape[2:]
    H = q_ref.shape[1]

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = lens_ref[b]
    s_start = si * block_s

    @pl.when(s_start < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                      # [H, Dh]
        k = k_ref[0, 0].astype(jnp.float32).reshape(bs * KH, Dh)
        v = v_ref[0, 0].astype(jnp.float32).reshape(bs * KH, Dh)
        # own[pos, kh, h]: q-head h attends with kv-head kh (GQA groups)
        own = (jax.lax.broadcasted_iota(jnp.int32, (bs, KH, H), 1)
               == jax.lax.broadcasted_iota(jnp.int32, (bs, KH, H), 2)
               // (H // KH))
        s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(own, s.reshape(bs, KH, H), 0.0).sum(axis=1) * scale
        pos = s_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(pos >= length, NEG_INF, s)              # [bs, H]
        m_prev = m_ref[...]                                   # [1, H]
        m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=0, keepdims=True)
        p_own = jnp.where(own, p[:, None, :], 0.0).reshape(bs * KH, H)
        pv = jax.lax.dot_general(p_own, v, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha.reshape(H, 1) + pv  # [H, Dh]
        m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30).reshape(H, 1)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _attend(q, k_all, v_all, lens, layer, *, block_s: int, interpret: bool):
    """q [B, H, Dh]; caches [L, B, S, KH, Dh] with S a multiple of
    block_s -> [B, H, Dh]."""
    B, H, Dh = q.shape
    S, KH = k_all.shape[2], k_all.shape[3]

    def kv_map(b, si, lens, layer):
        last = jnp.maximum(lens[b] - 1, 0) // block_s
        return layer[0], b, jnp.minimum(si, last), 0, 0

    kernel = functools.partial(_kernel, scale=1.0 / math.sqrt(Dh),
                               block_s=block_s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, S // block_s),
        in_specs=[
            pl.BlockSpec((1, H, Dh), lambda b, si, lens, layer: (b, 0, 0)),
            pl.BlockSpec((1, 1, block_s, KH, Dh), kv_map),
            pl.BlockSpec((1, 1, block_s, KH, Dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, H, Dh),
                               lambda b, si, lens, layer: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, Dh), jnp.float32),
            pltpu.VMEM((1, H), jnp.float32),
            pltpu.VMEM((1, H), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        interpret=interpret,
    )(lens.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      q, k_all, v_all)


def stacked_fits(cache_shape, block_s: int = 256) -> bool:
    """Whether `decode_attention_stacked` reads a [L, B, S, KH, Dh] cache
    without padding it: its positions tile by the block."""
    S = cache_shape[2]
    return S % min(block_s, S) == 0


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention_stacked(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
                             lens: jax.Array, layer: jax.Array, *,
                             block_s: int = 256,
                             interpret: bool = False) -> jax.Array:
    """q: [B, H, Dh]; caches [L, B, S, KH, Dh]; lens [B]; layer: scalar
    i32 -> out [B, H, Dh], attending to layer `layer` of the caches where
    they lie.  S must be a multiple of the block (`stacked_fits`)."""
    S = k_all.shape[2]
    if not stacked_fits(k_all.shape, block_s):
        raise ValueError(f"{S} positions do not tile by block_s={block_s}")
    return _attend(q, k_all, v_all, lens, layer, block_s=min(block_s, S),
                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lens: jax.Array, *, block_s: int = 256,
                     interpret: bool = False) -> jax.Array:
    """q: [B, H, Dh]; caches [B, S, KH, Dh]; lens [B] -> out [B, H, Dh].

    A length that does not tile by the block is zero-padded, which costs a
    copy of the cache."""
    S = k_cache.shape[1]
    block_s = min(block_s, S)
    pad_s = -S % block_s
    if pad_s:
        widths = ((0, 0), (0, pad_s), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
    return _attend(q, k_cache[None], v_cache[None], lens, jnp.int32(0),
                   block_s=block_s, interpret=interpret)
