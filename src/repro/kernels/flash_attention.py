"""Flash attention (prefill) — Pallas TPU kernel.

Blocked online-softmax attention with GQA, causal masking and block-level
causal skipping.  TPU-native design decisions (vs. a CUDA port):

* the grid's innermost (sequential) dimension walks KV blocks, carrying the
  running (acc, m, l) in VMEM scratch — TPU grid steps execute in order on
  one core, so the scratch IS the inter-block recurrence, no atomics;
* BlockSpecs tile HBM->VMEM so each step touches (block_q × head_dim) of Q
  and (block_k × head_dim) of K/V — MXU-aligned (multiples of 128 for f32
  lanes / 8 sublanes; head_dim up to 128 fits one register tile);
* fully-masked causal blocks are skipped with @pl.when (no MXU work), which
  halves the FLOPs of the naive full-matrix schedule;
* heads go ahead of the sequence inside the wrapper, so every block's last
  two dims are (rows, Dh) — the (8, 128) tiling Mosaic demands.  A block of
  one head taken from a [.., S, H, Dh] array would put that head (1 of H)
  second-minor, which the TPU compiler refuses.  The transposes are O(S)
  against the kernel's O(S²) work.

Layout: q [B, Sq, H, Dh]; k/v [B, Sk, KH, Dh]; H = KH·G (heads-major inside).
Grid: (B, H, Sq/bq, Sk/bk); K/V index_map sends q-head h to kv-head h//G.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            causal: bool, scale: float, block_q: int, block_k: int,
            seq_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    # block-level causal skip: block is live unless every kv pos > every q pos
    live = jnp.logical_or(not causal, k_start <= q_start + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, Dh]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, Dh]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq,bk]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q,
                                                              block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q,
                                                              block_k), 1)
        invalid = kpos >= seq_k                                # kv padding
        if causal:
            invalid = jnp.logical_or(invalid, kpos > qpos)
        s = jnp.where(invalid, NEG_INF, s)

        m_prev = m_ref[...]                                  # [bq, 1]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jax.Array:
    """q: [B, Sq, H, Dh]; k/v: [B, Sk, KH, Dh] -> [B, Sq, H, Dh]."""
    B, Sq, H, Dh = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = 1.0 / math.sqrt(Dh)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)
    q = q.transpose(0, 2, 1, 3)                   # heads-major [B, H, S, Dh]
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    if Sq % block_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, nq * block_q - Sq), (0, 0)))
    if Sk % block_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, nk * block_k - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, nk * block_k - Sk), (0, 0)))

    kernel = functools.partial(_kernel, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k, seq_k=Sk)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, Dh),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, Dh),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dh),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * block_q, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dh), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)
