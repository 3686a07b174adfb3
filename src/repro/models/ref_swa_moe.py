"""Plain reference for decoder-only models with sliding-window and full
attention mixed and a routed-expert MLP (Mellum2's block): the forward
pass in float32 ``jax.numpy`` under ``default_matmul_precision("highest")``,
written from the published description and independent of the served
path — no cache, no kernels, no batching, no capacity.

Per layer l, of kind ``cfg.layer_pattern[l % period]``:

    h = RMSNorm(x);  q, k, v = h Wq, h Wk, h Wv    (GQA: H q-heads, KH kv)
    q, k = rope(q), rope(k)     rotate-half; default frequencies θ^(-2i/d)
                                on sliding layers, YaRN's on full layers,
                                cos and sin times YaRN's attention factor
    a_ij = q_i·k_j / √d for i - W < j ≤ i (sliding) or j ≤ i (full)
    x = x + softmax(a) v Wo
    h = RMSNorm(x);  p = softmax(h Wr);  top-k experts, gates renormalised
    x = x + Σ_e g_e · (silu(h Wg_e) ⊙ h Wu_e) Wd_e     (every expert, dense)

then a final RMSNorm and the untied head.  Weights are the program's own
parameter tree (``models.init``'s layout), read in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


def rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def inv_freq(cfg: ModelConfig, kind: str) -> np.ndarray:
    """Rotary frequencies [head_dim // 2] in float64: the default ones, or
    on full layers YaRN's (arXiv:2309.00071 §3.2, NTK-by-parts): dimension
    i turns r_i = L / (2π θ^(2i/d)) times over the original context L; it
    is interpolated (divided by the factor) where r_i < beta_slow, kept
    where r_i > beta_fast, and blended linearly between, the bounds rounded
    outward to whole dimensions as Hugging Face rounds them."""
    d = cfg.resolved_head_dim
    base = 1.0 / cfg.rope_theta ** (np.arange(0, d, 2) / d)
    y = cfg.yarn
    if kind != "full" or y is None:
        return base

    def dim_of(rotations):   # the dimension that turns `rotations` times
        return (d * math.log(y.original_max_position
                             / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))
    lo = max(math.floor(dim_of(y.beta_fast)), 0)
    hi = min(math.ceil(dim_of(y.beta_slow)), d - 1)
    hi = hi + 0.001 if hi == lo else hi
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0.0, 1.0)
    return base / y.factor * ramp + base * (1.0 - ramp)


def rope_scale(cfg: ModelConfig, kind: str) -> float:
    return cfg.yarn.attention_factor if (kind == "full" and cfg.yarn) else 1.0


def rope(t, positions, freqs, scale):
    """t [S, heads, d] rotated by angle positions ⊗ freqs (rotate-half)."""
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(freqs,
                                                               jnp.float32)
    c = (jnp.cos(ang) * scale)[:, None, :]
    s = (jnp.sin(ang) * scale)[:, None, :]
    half = t.shape[-1] // 2
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], axis=-1)


def attention(x, lp, cfg: ModelConfig, kind: str, positions):
    """One attention block on x [S, d] (its input norm included)."""
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    S = x.shape[0]
    h = rms(x, lp["ln1"]["scale"], cfg.norm_eps)
    q = (h @ lp["attn"]["wq"]).reshape(S, H, Dh)
    k = (h @ lp["attn"]["wk"]).reshape(S, KH, Dh)
    v = (h @ lp["attn"]["wv"]).reshape(S, KH, Dh)
    freqs, scale = inv_freq(cfg, kind), rope_scale(cfg, kind)
    q, k = rope(q, positions, freqs, scale), rope(k, positions, freqs, scale)
    k = jnp.repeat(k, H // KH, axis=1)          # head h reads kv head h // G
    v = jnp.repeat(v, H // KH, axis=1)
    a = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(Dh)
    i, j = positions[:, None], positions[None, :]
    seen = j <= i
    if kind == "sliding":
        seen = seen & (j > i - cfg.sliding_window)
    a = jnp.where(seen[None], a, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(a, axis=-1), v)
    return o.reshape(S, H * Dh) @ lp["attn"]["wo"]


def experts(x, lp, cfg: ModelConfig):
    """The routed MLP on x [S, d] (its input norm included): every expert
    computed for every token, weighted by its gate (zero off the top-k)."""
    m = cfg.moe
    h = rms(x, lp["ln2"]["scale"], cfg.norm_eps)
    p = jax.nn.softmax(h @ lp["mlp"]["router"], axis=-1)
    top, idx = jax.lax.top_k(p, m.top_k)
    top = top / top.sum(axis=-1, keepdims=True)      # norm_topk_prob
    out = jnp.zeros_like(x)
    for e in range(m.num_experts):
        g = jnp.sum(jnp.where(idx == e, top, 0.0), axis=-1)      # [S]
        y = jax.nn.silu(h @ lp["mlp"]["w_gate"][e]) * (h @ lp["mlp"]["w_up"][e])
        out = out + g[:, None] * (y @ lp["mlp"]["w_down"][e])
    return out


def forward(params: dict, tokens, cfg: ModelConfig) -> jax.Array:
    """Logits [S, V] (float32) of one token sequence [S]."""
    kinds = cfg.layer_pattern or ("full",)
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        tokens = jnp.asarray(tokens)
        positions = jnp.arange(tokens.shape[0])
        x = w["embed"]["table"][tokens]
        for layer in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[layer], w["layers"])
            kind = kinds[layer % len(kinds)]
            x = x + attention(x, lp, cfg, kind, positions)
            x = x + experts(x, lp, cfg)
        x = rms(x, w["final_norm"]["scale"], cfg.norm_eps)
        return x @ w["embed"]["unembed"]
