"""Mamba2 SSD chunked scan — Pallas TPU kernel (arXiv:2405.21060 §6).

TPU adaptation of the SSD algorithm (the CUDA version leans on warp-level
matmul fragments; here the unit of work is a VMEM-resident chunk):

* grid (B, H/bh, L/Q) — the innermost dimension walks chunks IN ORDER; the
  running inter-chunk state S [bh, N, P] lives in VMEM scratch, making the
  sequential-grid recurrence the inter-chunk scan (no cross-core sync);
* per step and head, the quadratic intra-chunk term runs on the MXU:
  (C·Bᵀ ⊙ decay) @ (dt·x), with Q×Q attention-like scores;
* B/C are per-group (GVA); the group tile is shared by the head block, so
  head-blocks never re-read B/C from HBM;
* the wrapper lays every operand out heads-major and precomputes the
  elementwise terms (dt·x and the within-chunk cumulative log-decay), so
  each block's last two dims are (Q, P), (bh, Q) or (Q, N) — the (8, 128)
  tiling Mosaic demands — and the kernel body is 2-D matmuls per head.

Layouts: x [B, L, H, P]; dt [B, L, H]; A [H]; Bm/Cm [B, L, G, N] with G=1
(the assigned configs all use a single B/C group).
Returns (y [B, L, H, P], final_state [B, H, N, P]).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the state recurrence is specified in float32: contract at float32 on the
# MXU too (Mosaic's default contracts float32 operands at lower precision)
_F32 = jax.lax.Precision.HIGHEST


def _kernel(xdt_ref, cum_ref, b_ref, c_ref, y_ref, fs_ref, s_ref, *,
            block_h: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    Bm = b_ref[0].astype(jnp.float32)                # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)                # [Q, N]
    cum = cum_ref[0]                                 # [bh, Q] log-decay
    cum_t = cum.T                                    # [Q, bh]
    chunk = Bm.shape[0]
    iq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = iq >= jq
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())), precision=_F32,
                             preferred_element_type=jnp.float32)  # [Q, Q]

    for h in range(block_h):
        xdt = xdt_ref[0, h]                          # [Q, P] (dt·x, f32)
        li = cum_t[:, h:h + 1]                       # [Q, 1]
        lj = cum[h:h + 1, :]                         # [1, Q]
        seg = jnp.sum(jnp.where(last, lj, 0.0), axis=1,
                      keepdims=True)                 # [1, 1] chunk total
        # ---- intra-chunk: min-clamp is exact for valid (i>=j) entries and
        # prevents exp overflow on masked ones (see models/mamba2.py)
        M = jnp.where(tril, cb * jnp.exp(jnp.minimum(li - lj, 0.0)), 0.0)
        y = jax.lax.dot_general(M, xdt, (((1,), (0,)), ((), ())),
                                precision=_F32,
                                preferred_element_type=jnp.float32)
        # ---- inter-chunk: y[i,p] += Σ_n C[i,n]·S_prev[n,p]·exp(cum[i])
        S_prev = s_ref[h]                            # [N, P]
        y = y + jax.lax.dot_general(
            Cm, S_prev, (((1,), (0,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32) * jnp.exp(li)
        y_ref[0, h] = y.astype(y_ref.dtype)
        # ---- state update: S_c[n,p] = Σ_j B[j,n]·xdt[j,p]·exp(seg-cum[j])
        xw = xdt * jnp.exp(seg - li)                 # [Q, P]
        S_c = jax.lax.dot_general(Bm, xw, (((0,), (0,)), ((), ())),
                                  precision=_F32,
                                  preferred_element_type=jnp.float32)
        s_ref[h] = S_prev * jnp.exp(seg) + S_c

    @pl.when(ci == nc - 1)
    def _emit_state():
        fs_ref[0] = s_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "block_h", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 128, block_h: int = 8,
             interpret: bool = False):
    """x [B,L,H,P]; dt [B,L,H]; A [H]; Bm/Cm [B,L,1,N] -> (y, final_state)."""
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    assert Bm.shape[2] == 1, "kernel assumes a single B/C group (G=1)"
    chunk = min(chunk, L)
    block_h = min(block_h, H)
    nc = pl.cdiv(L, chunk)
    nh = pl.cdiv(H, block_h)
    Lp = nc * chunk
    f32 = jnp.float32
    dt = dt.astype(f32)
    # padded tail positions get dt=0: decay exp(0)=1 and dt·x=0
    pad = ((0, 0), (0, Lp - L))
    xdt = jnp.pad(x.astype(f32) * dt[..., None], pad + ((0, 0), (0, 0)))
    dA = jnp.pad(dt * A.astype(f32), pad + ((0, 0),))
    cum = jnp.cumsum(dA.reshape(B, nc, chunk, H), axis=2)  # within-chunk
    cum = cum.reshape(B, Lp, H).transpose(0, 2, 1)           # [B, H, Lp]
    xdt = xdt.transpose(0, 2, 1, 3)                          # [B, H, Lp, P]
    Bm = jnp.pad(Bm[:, :, 0], pad + ((0, 0),))               # [B, Lp, N]
    Cm = jnp.pad(Cm[:, :, 0], pad + ((0, 0),))

    kernel = functools.partial(_kernel, block_h=block_h)
    y, fs = pl.pallas_call(
        kernel,
        grid=(B, nh, nc),
        in_specs=[
            pl.BlockSpec((1, block_h, chunk, P),
                         lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, block_h, chunk), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_h, chunk, P),
                         lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, block_h, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lp, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_h, N, P), jnp.float32)],
        interpret=interpret,
    )(xdt, cum, Bm, Cm)
    return y[:, :, :L].transpose(0, 2, 1, 3), fs
