"""Core transformer layers: RMSNorm, RoPE/M-RoPE, GQA/MQA attention, MLP.

Pure-functional: params are nested dicts of jnp arrays; every `init_*` has a
matching `*_apply`.  Attention has three implementations, selected by
RunConfig.attention_impl:

* ``naive``   — full score matrix (tests/smoke only; O(S²) memory)
* ``chunked`` — lax.scan online-softmax over KV chunks (flash-attention
                algorithm in pure JAX; bounded HLO temps — the dry-run path)
* ``pallas``  — the TPU kernel in repro.kernels (validated interpret=True)

Numerics: params in cfg.param_dtype (default bf16), attention logits and
softmax accumulation in f32, residual stream in activation dtype.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.act_sharding import constrain


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm_apply(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE + Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------

def rope_angles(positions: jax.Array, head_dim: int, theta: float,
                inv_freq=None) -> jax.Array:
    """positions [..., S] -> angles [..., S, head_dim//2] (f32).
    ``inv_freq`` replaces the default frequencies θ^(-2i/head_dim)."""
    half = head_dim // 2
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                    / half))
    return positions.astype(jnp.float32)[..., None] * inv_freq


def yarn_inv_freq(head_dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN's NTK-by-parts frequencies [head_dim//2] (f32), as Hugging
    Face's ``_compute_yarn_parameters`` computes them: dimensions that turn
    fewer than ``beta_slow`` times over the original context are
    interpolated by ``factor``, those that turn more than ``beta_fast``
    times are kept, and a linear ramp blends the ones between.  Cos and sin
    are scaled by ``yarn.attention_factor`` where they are applied."""
    f32 = np.float32
    dim = head_dim

    def correction_dim(rotations):
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = f32(theta) ** (np.arange(0, dim, 2, dtype=f32) / f32(dim))
    extrapolation = f32(1.0) / pos_freqs
    interpolation = f32(1.0) / (f32(yarn.factor) * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=f32) - f32(low))
                   / f32(high - low), 0, 1).astype(f32)
    keep = f32(1) - ramp
    return (interpolation * (1 - keep) + extrapolation * keep).astype(f32)


def mrope_angles(positions: jax.Array, head_dim: int, theta: float,
                 sections: tuple) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    positions: [B, 3, S] — (temporal, height, width) position ids.  The
    head_dim//2 frequency slots are partitioned into `sections`; slots in
    section j take their position from stream j.  For pure text the three
    streams are identical and M-RoPE degrades to 1-D RoPE (paper 2409.12191).
    """
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    chunks = []
    start = 0
    for j, width in enumerate(sections):
        pos_j = positions[:, j, :]                          # [B, S]
        chunks.append(pos_j.astype(jnp.float32)[..., None]
                      * inv_freq[start:start + width])      # [B, S, width]
        start += width
    return jnp.concatenate(chunks, axis=-1)                 # [B, S, half]


def apply_rope(x: jax.Array, angles: jax.Array,
               scale: float = 1.0) -> jax.Array:
    """x: [B, S, H, Dh]; angles: [B, S, Dh//2] (broadcast over heads);
    ``scale`` multiplies cos and sin (YaRN's attention factor)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = jnp.cos(angles)[..., None, :]   # [B, S, 1, half]
    sin = jnp.sin(angles)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# Attention cores
# ---------------------------------------------------------------------------

_NEG_INF = -2.0e30


def naive_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool, q_offset: int = 0,
                    window: int = 0) -> jax.Array:
    """Reference attention.  q: [B,Sq,H,Dh], k/v: [B,Sk,KH,Dh] with H=KH*G.
    ``window`` > 0 (causal only): query i sees key j iff i - window < j."""
    B, Sq, H, Dh = q.shape
    KH = k.shape[2]
    G = H // KH
    qg = q.reshape(B, Sq, KH, G, Dh).astype(jnp.float32)
    scale = 1.0 / math.sqrt(Dh)
    s = jnp.einsum("bqkgd,bckd->bqkgc", qg, k.astype(jnp.float32)) * scale
    if causal:
        qpos = q_offset + jnp.arange(Sq)
        kpos = jnp.arange(k.shape[1])
        mask = kpos[None, :] > qpos[:, None]                # [Sq, Sk]
        if window:
            mask = mask | (kpos[None, :] <= qpos[:, None] - window)
        s = jnp.where(mask[None, :, None, None, :], _NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkgc,bckd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, chunk: int = 1024,
                      q_offset: int = 0, window: int = 0) -> jax.Array:
    """Online-softmax attention over KV chunks (flash algorithm, pure JAX).

    Memory: O(Sq·H·Dh + Sq·H·chunk) instead of O(Sq·Sk·H).  This is the
    implementation the dry-run lowers — honest FLOPs, bounded temps.
    ``window`` > 0 (causal only): query i sees key j iff i - window < j ≤ i;
    every chunk is still visited, the keys outside the window masked.
    """
    B, Sq, H, Dh = q.shape
    Sk = k.shape[1]
    KH = k.shape[2]
    G = H // KH
    scale = 1.0 / math.sqrt(Dh)
    chunk = min(chunk, Sk)
    n_chunks = cdiv(Sk, chunk)
    pad = n_chunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qg = (q.reshape(B, Sq, KH, G, Dh) * scale).astype(q.dtype)
    qpos = q_offset + jnp.arange(Sq)

    def body(carry, idx):
        acc, m, l = carry
        ks = jax.lax.dynamic_slice_in_dim(k, idx * chunk, chunk, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, idx * chunk, chunk, axis=1)
        s = jnp.einsum("bqkgd,bckd->bqkgc", qg, ks,
                       preferred_element_type=jnp.float32)  # [B,Sq,KH,G,C]
        kpos = idx * chunk + jnp.arange(chunk)
        invalid = kpos[None, :] >= Sk                       # padding
        if causal:
            invalid = invalid | (kpos[None, :] > qpos[:, None])
        if window:
            invalid = invalid | (kpos[None, :] <= qpos[:, None] - window)
        s = jnp.where(invalid[None, :, None, None, :], _NEG_INF, s)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p.astype(v.dtype), vs,
            preferred_element_type=jnp.float32)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, Sq, KH, G, Dh), jnp.float32)
    m0 = jnp.full((B, Sq, KH, G), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, KH, G), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def decode_attention_chunked(q: jax.Array, k_cache: jax.Array,
                             v_cache: jax.Array, cache_len,
                             chunk: int = 2048) -> jax.Array:
    """Flash-decoding in pure JAX: online softmax over cache chunks.

    Never materializes the [B, H, S] score tensor — the scan body touches
    one [B, H, chunk] tile at a time, so HBM traffic approaches the
    irreducible cache read (the jnp analogue of kernels/decode_attention).
    q: [B, H, Dh]; caches [B, S, KH, Dh]; cache_len scalar or [B(,1)].
    """
    B, H, Dh = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    scale = 1.0 / math.sqrt(Dh)
    qg = (q.reshape(B, KH, G, Dh) * scale)
    lens = jnp.asarray(cache_len).reshape(-1, 1)    # [B or 1, 1]
    chunk = min(chunk, S)
    n_chunks = cdiv(S, chunk)
    pad = n_chunks * chunk - S
    kp = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k_cache
    vp = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v_cache

    def body(carry, idx):
        acc, m, l = carry
        ks = jax.lax.dynamic_slice_in_dim(kp, idx * chunk, chunk, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vp, idx * chunk, chunk, axis=1)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, ks,
                       preferred_element_type=jnp.float32)  # [B,KH,G,chunk]
        pos = idx * chunk + jnp.arange(chunk)
        valid = pos[None, :] < lens                          # [B or 1, chunk]
        s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgs,bskd->bkgd", p.astype(vs.dtype), vs,
            preferred_element_type=jnp.float32)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, KH, G, Dh), jnp.float32)
    m0 = jnp.full((B, KH, G), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KH, G), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, H, Dh).astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array | int) -> jax.Array:
    """Single-token attention against a KV cache.

    q: [B, H, Dh]; k_cache/v_cache: [B, S, KH, Dh]; cache_len: filled length.
    Scores stay [B, H, S] — small; softmax reduction over a (possibly
    model-axis-sharded) S is handled by GSPMD with an all-reduce.
    """
    B, H, Dh = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    scale = 1.0 / math.sqrt(Dh)
    qg = (q.reshape(B, KH, G, Dh) * scale)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32)      # [B,KH,G,S]
    lens = jnp.asarray(cache_len)
    if lens.ndim == 0:
        valid = (jnp.arange(S) < lens)[None, :]             # [1, S]
    else:
        valid = jnp.arange(S)[None, :] < lens.reshape(-1, 1)  # [B, S]
    s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (projections + norm + rope + core)
# ---------------------------------------------------------------------------

def init_attention(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    d, H, KH, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    so = 1.0 / math.sqrt(H * Dh)
    p = {
        "wq": (jax.random.normal(k1, (d, H * Dh)) * s).astype(dtype),
        "wk": (jax.random.normal(k2, (d, KH * Dh)) * s).astype(dtype),
        "wv": (jax.random.normal(k3, (d, KH * Dh)) * s).astype(dtype),
        "wo": (jax.random.normal(k4, (H * Dh, d)) * so).astype(dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(Dh, dtype)
        p["k_norm"] = init_rmsnorm(Dh, dtype)
    return p


def attention_qkv(params: dict, x: jax.Array, cfg: ModelConfig,
                  angles: jax.Array, rope_scale: float = 1.0):
    """Project + (qk-norm) + rope.  Returns q [B,S,H,Dh], k/v [B,S,KH,Dh].
    ``rope_scale`` multiplies rope's cos and sin (YaRN)."""
    B, S, _ = x.shape
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(B, S, H, Dh)
    k = (x @ params["wk"]).reshape(B, S, KH, Dh)
    v = (x @ params["wv"]).reshape(B, S, KH, Dh)
    if cfg.qk_norm:
        q = rmsnorm_apply(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm_apply(params["k_norm"], k, cfg.norm_eps)
    if angles is not None:
        q = apply_rope(q, angles, rope_scale)
        k = apply_rope(k, angles, rope_scale)
    # attention operates on the full sequence per head shard: under
    # sequence parallelism the seq dim is gathered at this boundary
    # (Megatron-style), so q/k/v pin heads but leave seq unsharded
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    return q, k, v


def attention_apply(params: dict, x: jax.Array, cfg: ModelConfig, *,
                    angles: jax.Array | None, causal: bool = True,
                    impl: str = "chunked", chunk: int = 1024,
                    kv_override: tuple | None = None,
                    rope_scale: float = 1.0, window: int = 0) -> jax.Array:
    """Full attention block on [B, S, D].  kv_override: cross-attention;
    ``window`` > 0: sliding-window attention (naive and chunked only)."""
    B, S, _ = x.shape
    H, Dh = cfg.n_heads, cfg.resolved_head_dim
    q, k, v = attention_qkv(params, x, cfg, angles, rope_scale)
    if kv_override is not None:
        k, v = kv_override
    if impl == "naive":
        o = naive_attention(q, k, v, causal=causal, window=window)
    elif impl == "pallas":
        if window:
            raise NotImplementedError("the Pallas flash kernel has no "
                                      "sliding window")
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, k, v, causal=causal)
    else:
        o = chunked_attention(q, k, v, causal=causal, chunk=chunk,
                              window=window)
    o = constrain(o, "batch", None, "heads", None)
    return o.reshape(B, S, H * Dh) @ params["wo"]


def attention_decode_apply(params: dict, x: jax.Array, cfg: ModelConfig, *,
                           angles: jax.Array, k_cache: jax.Array,
                           v_cache: jax.Array, cache_len) -> tuple:
    """One-token decode.  x: [B, 1, D].  Returns (out [B,1,D], new_k, new_v).

    The new token's K/V ([B,1,KH,Dh]) are returned for the caller to insert
    into the cache (cache layout/update policy lives in repro.serve.kvcache).
    """
    B, S, _ = x.shape
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = attention_qkv(params, x, cfg, angles)
    # attend over cache plus the new token's own K/V appended logically:
    # the engine writes k/v into the cache at position cache_len *before*
    # calling, so attending over [0, cache_len] covers it.
    o = decode_attention(q[:, 0], k_cache, v_cache, cache_len)
    out = o.reshape(B, 1, H * Dh) @ params["wo"]
    return out, k, v


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeLU)
# ---------------------------------------------------------------------------

def init_mlp(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    if cfg.act == "silu":
        return {
            "w_gate": (jax.random.normal(k1, (d, f)) * s_in).astype(dtype),
            "w_up": (jax.random.normal(k2, (d, f)) * s_in).astype(dtype),
            "w_down": (jax.random.normal(k3, (f, d)) * s_out).astype(dtype),
        }
    return {
        "w_up": (jax.random.normal(k1, (d, f)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k2, (f, d)) * s_out).astype(dtype),
    }


def mlp_apply(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.act == "silu":
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    else:
        h = jax.nn.gelu(x @ params["w_up"])
    h = constrain(h, "batch", "seq", "hidden")
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"table": (jax.random.normal(k1, (cfg.vocab, cfg.d_model))
                   * 0.02).astype(dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = (jax.random.normal(k2, (cfg.d_model, cfg.vocab))
                        * 0.02).astype(dtype)
    return p


def embed_apply(params: dict, tokens: jax.Array, dtype,
                onehot: bool = False) -> jax.Array:
    """Token embedding lookup.

    ``onehot=True`` uses a one-hot matmul instead of a gather — required when
    the table is VOCAB-sharded (tied-embedding archs): XLA SPMD handles a
    sharded-contraction einsum cleanly, while a gather over a sharded vocab
    triggers involuntary full rematerialization (replicates the table).
    Untied archs shard the table on D, where the gather is communication-free.
    """
    table = params["table"].astype(dtype)
    if onehot:
        oh = jax.nn.one_hot(tokens, table.shape[0], dtype=dtype)
        return jnp.einsum("bsv,vd->bsd", oh, table)
    return table[tokens]


def unembed_apply(params: dict, x: jax.Array) -> jax.Array:
    """Logits in f32 (loss numerics)."""
    if "unembed" in params:
        w = params["unembed"]
    else:
        w = params["table"].T
    logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                        w.astype(jnp.float32))
    return constrain(logits, "batch", "seq", "vocab")
