"""Model assembly for all assigned architecture families.

Families:
  dense   — decoder-only LM (GQA/MQA attention + MLP)         [qwen3, minitron,
            granite-34b, qwen2-vl backbone]
  moe     — dense skeleton with MoE FFN                        [grok-1, granite-moe]
  ssm     — attention-free Mamba2 (SSD) stack                  [mamba2-370m]
  hybrid  — Mamba2 backbone + weight-shared attention block
            applied every `period` layers                      [zamba2-2.7b]
  encdec  — Whisper backbone: bidirectional encoder over stub
            frame embeddings + causal decoder w/ cross-attn    [whisper-large-v3]

Layer stacks are `lax.scan` over stacked parameters (compile-time O(1) in
depth — essential for the 40-cell dry-run).  Remat policy wraps the scanned
layer body.  A dense/moe model with a layer pattern (``cfg.layer_pattern``:
sliding-window and full attention mixed) scans over periods of the pattern,
each period's layers unrolled; its serving cache holds two kinds of KV:
the full layers' ``k``/``v`` [n_full, B, S, KH, Dh] and the sliding layers'
rings ``k_win``/``v_win`` [n_sliding, B, W, KH, Dh], where position p lies
at p mod W.  Every family exposes:

  init(key, cfg)                         -> params
  forward(params, batch, cfg, run)       -> (logits, aux)      # train/prefill
  init_cache(cfg, batch, max_seq)        -> cache pytree
  decode_step(params, cache, batch, cfg, run) -> (logits, new_cache)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.distributed.act_sharding import constrain, whole_on_one_tpu
from repro.kernels import ops

from . import layers as L
from . import mamba2 as M
from . import moe as X


def _dec_attn(run: RunConfig):
    """Decode attention core per RunConfig (direct vs flash-decoding scan)."""
    if run.decode_attn_impl == "chunked":
        return functools.partial(L.decode_attention_chunked,
                                 chunk=run.attention_chunk)
    return L.decode_attention


def _stacked_attn(run: RunConfig, cache_shape):
    """Decode attention over layer l of the stacked cache [L, B, S, KH, Dh],
    chosen by where the cache lives.  On a TPU that holds the whole cache
    (no mesh, or a mesh of one device) the Pallas kernel reads the layer in
    place.  Elsewhere — a mesh that splits the cache, whose softmax over a
    split sequence GSPMD reduces, or off the TPU — the jnp read slices the
    layer out first."""
    if whole_on_one_tpu() and ops.stacked_fits(cache_shape):
        return lambda q, kc_all, vc_all, l, lens: ops.decode_attention_stacked(
            q, kc_all, vc_all, lens, l, interpret=False)
    attn = _dec_attn(run)
    return lambda q, kc_all, vc_all, l, lens: attn(
        q, kc_all[l], vc_all[l], lens[:, None])


def _adtype(cfg: ModelConfig):
    return jnp.dtype(cfg.activation_dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _remat(fn, run: RunConfig):
    if run.remat == "none":
        return fn
    if run.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn)


def _angles(cfg: ModelConfig, positions: jax.Array) -> jax.Array | None:
    """positions: [B, S] (or [B, 3, S] for M-RoPE)."""
    if cfg.attn_free:
        return None
    Dh = cfg.resolved_head_dim
    if cfg.mrope:
        if positions.ndim == 2:  # text-only: all three streams identical
            positions = jnp.broadcast_to(positions[:, None, :],
                                         (positions.shape[0], 3,
                                          positions.shape[1]))
        return L.mrope_angles(positions, Dh, cfg.rope_theta,
                              cfg.mrope_sections)
    if positions.ndim == 3:
        positions = positions[:, 0, :]
    return L.rope_angles(positions, Dh, cfg.rope_theta)


def _ropes(cfg: ModelConfig, positions: jax.Array) -> dict:
    """Rotary (angles, cos/sin scale) per attention kind: YaRN's on full
    layers where the config has it, the default on every other."""
    ang = _angles(cfg, positions)
    ropes = {"full": (ang, 1.0), "sliding": (ang, 1.0)}
    if cfg.yarn is not None:
        Dh = cfg.resolved_head_dim
        inv = jnp.asarray(L.yarn_inv_freq(Dh, cfg.rope_theta, cfg.yarn))
        if positions.ndim == 3:
            positions = positions[:, 0, :]
        ropes["full"] = (L.rope_angles(positions, Dh, cfg.rope_theta, inv),
                         cfg.yarn.attention_factor)
    return ropes


def _window(cfg: ModelConfig, kind: str) -> int:
    """Positions a layer of this kind attends to; 0 for all."""
    return cfg.sliding_window if kind == "sliding" else 0


def _ring_slot(pos: jax.Array, W: int) -> jax.Array:
    """Where position ``pos`` lies in a ring of W entries."""
    return pos % W


def _periods(layers: dict, period: int) -> dict:
    """Stacked layer params [L, ...] -> [L / period, period, ...]."""
    if period == 1:
        return layers
    return jax.tree.map(
        lambda a: a.reshape((a.shape[0] // period, period) + a.shape[1:]),
        layers)


def _member(lps: dict, j: int, period: int) -> dict:
    """Layer j of one period's params."""
    return lps if period == 1 else jax.tree.map(lambda a: a[j], lps)


def _kind_index(kinds: tuple, j: int, g):
    """Index of the period-g layer j among all layers of its kind."""
    per = kinds.count(kinds[j])
    rank = kinds[:j].count(kinds[j])
    return g if per == 1 else g * per + rank


def _split_experts(layers: dict) -> tuple[dict, dict | None]:
    """Stacked layer params -> (all but the routed experts' weights, those
    weights [L, E, ...]).  A scan that took the experts as xs would slice a
    layer's out for each grouped-matmul call, a copy of every expert
    matrix; kept whole, the kernel reads layer l where it lies."""
    mlp = layers["mlp"]
    if "router" not in mlp:
        return layers, None
    experts = {k: v for k, v in mlp.items() if k in X.EXPERT_WEIGHTS}
    rest = {k: v for k, v in mlp.items() if k not in X.EXPERT_WEIGHTS}
    return {**layers, "mlp": rest}, experts


def _sum_counters(counts: list):
    """Sum the per-layer MoE counters of one period (None without MoE)."""
    counts = [c for c in counts if c is not None]
    return functools.reduce(jnp.add, counts) if counts else None


# ===========================================================================
# Per-layer init/apply for attention-based layers
# ===========================================================================

def _init_attn_layer(key: jax.Array, cfg: ModelConfig) -> dict:
    dt = _pdtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": L.init_rmsnorm(cfg.d_model, dt),
        "attn": L.init_attention(k1, cfg, dt),
        "ln2": L.init_rmsnorm(cfg.d_model, dt),
    }
    if cfg.family == "moe" or (cfg.moe is not None and cfg.family != "hybrid"):
        p["mlp"] = X.init_moe(k2, cfg, dt)
    else:
        p["mlp"] = L.init_mlp(k2, cfg, dt)
    return p


def _attn_layer_apply(lp: dict, x: jax.Array, cfg: ModelConfig,
                      run: RunConfig, angles, causal: bool,
                      rope_scale: float = 1.0, window: int = 0):
    h = L.attention_apply(lp["attn"], L.rmsnorm_apply(lp["ln1"], x, cfg.norm_eps),
                          cfg, angles=angles, causal=causal,
                          impl=run.attention_impl, chunk=run.attention_chunk,
                          rope_scale=rope_scale, window=window)
    x = x + h
    xn = L.rmsnorm_apply(lp["ln2"], x, cfg.norm_eps)
    if "router" in lp["mlp"]:
        h2, aux = X.moe_apply(lp["mlp"], xn, cfg,
                              group_size=run.moe_group_size)
    else:
        h2 = L.mlp_apply(lp["mlp"], xn, cfg)
        aux = {}
    return x + h2, aux


def _stack_init(key: jax.Array, n: int, init_one):
    return jax.vmap(init_one)(jax.random.split(key, n))


# ===========================================================================
# dense / moe decoder-only LM
# ===========================================================================

def init_dense(key: jax.Array, cfg: ModelConfig) -> dict:
    ke, kl, kn = jax.random.split(key, 3)
    return {
        "embed": L.init_embedding(ke, cfg, _pdtype(cfg)),
        "layers": _stack_init(kl, cfg.n_layers,
                              lambda k: _init_attn_layer(k, cfg)),
        "final_norm": L.init_rmsnorm(cfg.d_model, _pdtype(cfg)),
    }


def forward_dense(params: dict, batch: dict, cfg: ModelConfig,
                  run: RunConfig, last_only: bool = False):
    tokens = batch["tokens"]                       # [B, S]
    B, S = tokens.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = constrain(L.embed_apply(params["embed"], tokens, _adtype(cfg),
                                onehot=cfg.tie_embeddings),
                  "batch", "seq", None)
    ropes = _ropes(cfg, positions)
    kinds = cfg.layer_period
    P = len(kinds)

    def period(x, lps):
        auxes = []
        for j, kind in enumerate(kinds):
            ang, scale = ropes[kind]
            x, aux = _attn_layer_apply(_member(lps, j, P), x, cfg, run, ang,
                                       causal=True, rope_scale=scale,
                                       window=_window(cfg, kind))
            x = constrain(x, "batch", "seq", None)
            auxes.append(_aux_vector(aux))
        return x, auxes[0] if P == 1 else jnp.stack(auxes)

    x, aux_stack = jax.lax.scan(_remat(period, run), x,
                                _periods(params["layers"], P))
    if P > 1:
        aux_stack = aux_stack.reshape(-1, aux_stack.shape[-1])
    if last_only:
        x = x[:, -1:]
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed_apply(params["embed"], x)
    return logits, _aux_unvector(aux_stack, cfg)


_AUX_KEYS = ("moe_load_balance", "moe_z_loss", "moe_drop_fraction")


def _aux_vector(aux: dict) -> jax.Array:
    return jnp.stack([aux.get(k, jnp.float32(0)) for k in _AUX_KEYS])


def _aux_unvector(aux_stack: jax.Array, cfg: ModelConfig) -> dict:
    sums = aux_stack.sum(axis=0)
    out = dict(zip(_AUX_KEYS, sums))
    if cfg.moe is not None:
        out["moe_drop_fraction"] = out["moe_drop_fraction"] / cfg.n_layers
    return out


# -- decode -----------------------------------------------------------------

def _n_layers_of(cfg: ModelConfig, kind: str) -> int:
    kinds = cfg.layer_period
    return cfg.n_layers // len(kinds) * kinds.count(kind)


def ring_size(cfg: ModelConfig, max_seq: int) -> int:
    """Positions a sliding layer's ring holds: the window, or the whole
    sequence where that is shorter."""
    return min(cfg.sliding_window, max_seq)


def init_cache_dense(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    KH, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (_n_layers_of(cfg, "full"), batch, max_seq, KH, Dh)
    cache = {
        "k": jnp.zeros(shape, _adtype(cfg)),
        "v": jnp.zeros(shape, _adtype(cfg)),
    }
    n_sliding = _n_layers_of(cfg, "sliding")
    if n_sliding:
        ring = (n_sliding, batch, ring_size(cfg, max_seq), KH, Dh)
        cache["k_win"] = jnp.zeros(ring, _adtype(cfg))
        cache["v_win"] = jnp.zeros(ring, _adtype(cfg))
    return cache


def _cache_insert(cache: jax.Array, kv: jax.Array, pos: jax.Array):
    """Per-slot scatter write: cache [B,S,KH,Dh], kv [B,1,KH,Dh], pos [B].

    Each sequence writes at ITS OWN position (continuous batching: slots
    join at different lengths).  Inactive slots pass pos >= S and their
    write is dropped (mode="drop") — the in-place scatter never touches
    them.  Lowers to an in-place scatter."""
    B = cache.shape[0]
    return cache.at[jnp.arange(B), pos].set(kv[:, 0].astype(cache.dtype),
                                            mode="drop")


def _cache_insert_at_layer(cache_all: jax.Array, kv: jax.Array,
                           layer_idx: jax.Array, pos: jax.Array):
    """Scatter one token's KV into the stacked cache [L,B,S,KH,Dh] at
    (layer_idx, b, pos[b]) — used when the cache rides in a scan carry."""
    B = cache_all.shape[1]
    lidx = jnp.broadcast_to(layer_idx, (B,))
    return cache_all.at[lidx, jnp.arange(B), pos].set(
        kv[:, 0].astype(cache_all.dtype), mode="drop")


def _active_pos(batch: dict, max_seq: int) -> jax.Array:
    """Write positions with inactive slots pushed out of range (dropped)."""
    seq_lens = batch["seq_lens"]
    active = batch.get("active")
    if active is None:
        return seq_lens
    return jnp.where(active, seq_lens, max_seq)


def _masked_state(new: jax.Array, old: jax.Array, active) -> jax.Array:
    """Recurrent-state update gate: inactive slots keep their old state
    (a lockstep decode step must not advance slots that are not decoding
    this tick — double-advancing corrupts SSM recurrences)."""
    if active is None:
        return new
    mask = active.reshape((active.shape[0],) + (1,) * (new.ndim - 1))
    return jnp.where(mask, new, old)


def decode_dense(params: dict, cache: dict, batch: dict, cfg: ModelConfig,
                 run: RunConfig, counters: bool = False):
    """One decode step.  batch: tokens [B,1], seq_lens [B] i32 (tokens
    already in each slot's cache).  Returns (logits [B, V], new_cache), and
    with ``counters`` the step's MoE counters (i32[4], summed over layers;
    None without experts) after them."""
    tokens = batch["tokens"]
    seq_lens = batch["seq_lens"]                   # [B]: per-slot position
    active = batch.get("active")
    B = tokens.shape[0]
    x = L.embed_apply(params["embed"], tokens, _adtype(cfg),
                       onehot=cfg.tie_embeddings)
    positions = seq_lens[:, None].astype(jnp.int32)
    ropes = _ropes(cfg, positions)
    kinds = cfg.layer_period
    P = len(kinds)
    H, Dh = cfg.n_heads, cfg.resolved_head_dim

    # Per kind: the stacked caches, each slot's write row (inactive slots
    # out of range, dropped) and the entries its read covers.  A ring holds
    # position p at p mod W and is read over min(p + 1, W) entries: the
    # window, in whatever order, since rope was applied before the write.
    caches = {"full": (cache["k"], cache["v"])}
    wpos = {"full": _active_pos(batch, cache["k"].shape[2])}
    lens = {"full": lambda: seq_lens + 1}
    if "k_win" in cache:
        W = cache["k_win"].shape[2]
        caches["sliding"] = (cache["k_win"], cache["v_win"])
        ring_pos = _ring_slot(seq_lens, W)
        wpos["sliding"] = (ring_pos if active is None
                           else jnp.where(active, ring_pos, W))
        seen = min(W, _window(cfg, "sliding"))
        lens["sliding"] = lambda: jnp.minimum(seq_lens + 1, seen)
    attend = {kind: _stacked_attn(run, kv[0].shape)
              for kind, kv in caches.items()}

    layers, experts = _split_experts(params["layers"])

    def _ffn(x, lp, layer):
        xn = L.rmsnorm_apply(lp["ln2"], x, cfg.norm_eps)
        if experts is not None:
            valid = None if active is None else active[:, None]
            h2, count = X.moe_dropless({**lp["mlp"], **experts}, xn, cfg,
                                       valid, layer=layer)
        else:
            h2, count = L.mlp_apply(lp["mlp"], xn, cfg), None
        return x + h2, count

    # The stacked caches ride the scan's CARRY, which XLA updates in place:
    # a step writes only the new token's row.  Taking them as xs and
    # returning ys instead copies the whole cache every step.
    def period(carry, inputs):
        x, caches = carry                          # kind -> [L, B, S, KH, Dh]
        lps, g = inputs
        counts = []
        for j, kind in enumerate(kinds):
            lp, l = _member(lps, j, P), _kind_index(kinds, j, g)
            kc_all, vc_all = caches[kind]
            ang, scale = ropes[kind]
            xn = L.rmsnorm_apply(lp["ln1"], x, cfg.norm_eps)
            q, k, v = L.attention_qkv(lp["attn"], xn, cfg, ang, scale)
            kc_all = _cache_insert_at_layer(kc_all, k, l, wpos[kind])
            vc_all = _cache_insert_at_layer(vc_all, v, l, wpos[kind])
            o = attend[kind](q[:, 0], kc_all, vc_all, l, lens[kind]())
            x = x + (o.reshape(B, 1, H * Dh) @ lp["attn"]["wo"])
            x, count = _ffn(x, lp, g * P + j)
            caches = {**caches, kind: (kc_all, vc_all)}
            counts.append(count)
        return (x, caches), _sum_counters(counts)

    (x, caches), counts = jax.lax.scan(
        period, (x, caches),
        (_periods(layers, P), jnp.arange(cfg.n_layers // P)))

    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed_apply(params["embed"], x)[:, 0]
    new = {"k": caches["full"][0], "v": caches["full"][1]}
    if "sliding" in caches:
        new["k_win"], new["v_win"] = caches["sliding"]
    if not counters:
        return logits, new
    return logits, new, None if counts is None else counts.sum(axis=0)


# ===========================================================================
# ssm (Mamba2)
# ===========================================================================

def init_ssm(key: jax.Array, cfg: ModelConfig) -> dict:
    ke, kl = jax.random.split(key)
    dt = _pdtype(cfg)

    def one(k):
        return {"ln": L.init_rmsnorm(cfg.d_model, dt),
                "mixer": M.init_mamba2(k, cfg, dt)}

    return {
        "embed": L.init_embedding(ke, cfg, dt),
        "layers": _stack_init(kl, cfg.n_layers, one),
        "final_norm": L.init_rmsnorm(cfg.d_model, dt),
    }


def forward_ssm(params: dict, batch: dict, cfg: ModelConfig, run: RunConfig,
                last_only: bool = False):
    tokens = batch["tokens"]
    x = L.embed_apply(params["embed"], tokens, _adtype(cfg),
                       onehot=cfg.tie_embeddings)
    impl = "pallas" if run.attention_impl == "pallas" else "chunked"

    def layer(x, lp):
        h = M.mamba2_apply(lp["mixer"],
                           L.rmsnorm_apply(lp["ln"], x, cfg.norm_eps),
                           cfg, impl=impl)
        return x + h, jnp.float32(0)

    x, _ = jax.lax.scan(_remat(layer, run), x, params["layers"])
    if last_only:
        x = x[:, -1:]
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    return L.unembed_apply(params["embed"], x), {}


def init_cache_ssm(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    dm = M.ssm_dims(cfg)
    return {
        "ssm": jnp.zeros((cfg.n_layers, batch, dm["nheads"], dm["state"],
                          dm["head_dim"]), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, batch, dm["conv_width"] - 1,
                           dm["conv_dim"]), _adtype(cfg)),
    }


def decode_ssm(params: dict, cache: dict, batch: dict, cfg: ModelConfig,
               run: RunConfig):
    tokens = batch["tokens"]
    active = batch.get("active")
    x = L.embed_apply(params["embed"], tokens, _adtype(cfg),
                       onehot=cfg.tie_embeddings)

    def layer(x, inputs):
        lp, ssm_state, conv_state = inputs
        h, ssm_new, conv_new = M.mamba2_decode(
            lp["mixer"], L.rmsnorm_apply(lp["ln"], x, cfg.norm_eps), cfg,
            ssm_state, conv_state)
        return x + h, (_masked_state(ssm_new, ssm_state, active),
                       _masked_state(conv_new, conv_state, active))

    x, (ssm_new, conv_new) = jax.lax.scan(
        layer, x, (params["layers"], cache["ssm"], cache["conv"]))
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed_apply(params["embed"], x)[:, 0]
    return logits, {"ssm": ssm_new, "conv": conv_new}


# ===========================================================================
# hybrid (Zamba2): Mamba2 backbone + shared attention block every `period`
# ===========================================================================

def _hybrid_groups(cfg: ModelConfig) -> tuple[int, int]:
    period = cfg.hybrid.period if cfg.hybrid else 6
    assert cfg.n_layers % period == 0, (cfg.n_layers, period)
    return cfg.n_layers // period, period


def init_hybrid(key: jax.Array, cfg: ModelConfig) -> dict:
    ke, kl, ks = jax.random.split(key, 3)
    dt = _pdtype(cfg)
    n_groups, period = _hybrid_groups(cfg)

    def one(k):
        return {"ln": L.init_rmsnorm(cfg.d_model, dt),
                "mixer": M.init_mamba2(k, cfg, dt)}

    return {
        "embed": L.init_embedding(ke, cfg, dt),
        "layers": _stack_init(kl, cfg.n_layers, one),   # [L, ...]
        "shared": _init_attn_layer(ks, cfg),            # weight-tied block
        "final_norm": L.init_rmsnorm(cfg.d_model, dt),
    }


def _group_params(params: dict, cfg: ModelConfig):
    """Reshape stacked mamba layers [L, ...] -> [G, period, ...]."""
    n_groups, period = _hybrid_groups(cfg)
    return jax.tree.map(
        lambda a: a.reshape((n_groups, period) + a.shape[1:]),
        params["layers"])


def forward_hybrid(params: dict, batch: dict, cfg: ModelConfig,
                   run: RunConfig, last_only: bool = False):
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = L.embed_apply(params["embed"], tokens, _adtype(cfg),
                       onehot=cfg.tie_embeddings)
    ang = _angles(cfg, positions)
    shared = params["shared"]
    impl = "pallas" if run.attention_impl == "pallas" else "chunked"

    def mamba_layer(x, lp):
        h = M.mamba2_apply(lp["mixer"],
                           L.rmsnorm_apply(lp["ln"], x, cfg.norm_eps),
                           cfg, impl=impl)
        return x + h, None

    def group(x, glp):
        x, _ = jax.lax.scan(mamba_layer, x, glp)
        x, _ = _attn_layer_apply(shared, x, cfg, run, ang, causal=True)
        return x, None

    x, _ = jax.lax.scan(_remat(group, run), x, _group_params(params, cfg))
    if last_only:
        x = x[:, -1:]
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    return L.unembed_apply(params["embed"], x), {}


def init_cache_hybrid(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    dm = M.ssm_dims(cfg)
    n_groups, _ = _hybrid_groups(cfg)
    KH, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "ssm": jnp.zeros((cfg.n_layers, batch, dm["nheads"], dm["state"],
                          dm["head_dim"]), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, batch, dm["conv_width"] - 1,
                           dm["conv_dim"]), _adtype(cfg)),
        # one KV cache per shared-block invocation
        "k": jnp.zeros((n_groups, batch, max_seq, KH, Dh), _adtype(cfg)),
        "v": jnp.zeros((n_groups, batch, max_seq, KH, Dh), _adtype(cfg)),
    }


def decode_hybrid(params: dict, cache: dict, batch: dict, cfg: ModelConfig,
                  run: RunConfig):
    tokens = batch["tokens"]
    seq_lens = batch["seq_lens"]
    B = tokens.shape[0]
    x = L.embed_apply(params["embed"], tokens, _adtype(cfg),
                       onehot=cfg.tie_embeddings)
    positions = seq_lens[:, None].astype(jnp.int32)
    ang = _angles(cfg, positions)
    shared = params["shared"]
    n_groups, period = _hybrid_groups(cfg)

    active = batch.get("active")
    wpos = _active_pos(batch, cache["k"].shape[2])

    def mamba_layer(x, inputs):
        lp, ssm_state, conv_state = inputs
        h, ssm_new, conv_new = M.mamba2_decode(
            lp["mixer"], L.rmsnorm_apply(lp["ln"], x, cfg.norm_eps), cfg,
            ssm_state, conv_state)
        return x + h, (_masked_state(ssm_new, ssm_state, active),
                       _masked_state(conv_new, conv_state, active))

    def group(x, inputs):
        glp, ssm_g, conv_g, kc, vc = inputs
        x, (ssm_g, conv_g) = jax.lax.scan(mamba_layer, x, (glp, ssm_g, conv_g))
        xn = L.rmsnorm_apply(shared["ln1"], x, cfg.norm_eps)
        q, k, v = L.attention_qkv(shared["attn"], xn, cfg, ang)
        kc = _cache_insert(kc, k, wpos)
        vc = _cache_insert(vc, v, wpos)
        o = _dec_attn(run)(q[:, 0], kc, vc, seq_lens[:, None] + 1)
        H, Dh = cfg.n_heads, cfg.resolved_head_dim
        x = x + (o.reshape(B, 1, H * Dh) @ shared["attn"]["wo"])
        xn = L.rmsnorm_apply(shared["ln2"], x, cfg.norm_eps)
        x = x + L.mlp_apply(shared["mlp"], xn, cfg)
        return x, (ssm_g, conv_g, kc, vc)

    glp = _group_params(params, cfg)
    ssm_g = cache["ssm"].reshape((n_groups, period) + cache["ssm"].shape[1:])
    conv_g = cache["conv"].reshape((n_groups, period) + cache["conv"].shape[1:])
    x, (ssm_new, conv_new, k_new, v_new) = jax.lax.scan(
        group, x, (glp, ssm_g, conv_g, cache["k"], cache["v"]))
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed_apply(params["embed"], x)[:, 0]
    return logits, {
        "ssm": ssm_new.reshape(cache["ssm"].shape),
        "conv": conv_new.reshape(cache["conv"].shape),
        "k": k_new, "v": v_new,
    }


# ===========================================================================
# encdec (Whisper backbone; conv/mel frontend is a stub per assignment)
# ===========================================================================

def _init_encdec_dec_layer(key: jax.Array, cfg: ModelConfig) -> dict:
    dt = _pdtype(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": L.init_rmsnorm(cfg.d_model, dt),
        "attn": L.init_attention(k1, cfg, dt),
        "ln_x": L.init_rmsnorm(cfg.d_model, dt),
        "xattn": L.init_attention(k2, cfg, dt),
        "ln2": L.init_rmsnorm(cfg.d_model, dt),
        "mlp": L.init_mlp(k3, cfg, dt),
    }


def init_encdec(key: jax.Array, cfg: ModelConfig) -> dict:
    ke, kenc, kdec, kp = jax.random.split(key, 4)
    dt = _pdtype(cfg)
    enc_layers = cfg.encoder_layers or cfg.n_layers
    return {
        "embed": L.init_embedding(ke, cfg, dt),
        # learned positional embedding for encoder frames (whisper-style)
        "enc_pos": (jax.random.normal(kp, (cfg.encoder_seq, cfg.d_model))
                    * 0.01).astype(dt),
        "encoder": _stack_init(kenc, enc_layers,
                               lambda k: _init_attn_layer(k, cfg)),
        "enc_norm": L.init_rmsnorm(cfg.d_model, dt),
        "decoder": _stack_init(kdec, cfg.n_layers,
                               lambda k: _init_encdec_dec_layer(k, cfg)),
        "final_norm": L.init_rmsnorm(cfg.d_model, dt),
    }


def encode(params: dict, frames: jax.Array, cfg: ModelConfig,
           run: RunConfig) -> jax.Array:
    """frames: [B, F, D] precomputed frame embeddings (conv frontend STUB)."""
    x = frames.astype(_adtype(cfg)) + params["enc_pos"][None, :frames.shape[1]]

    def layer(x, lp):
        x, _ = _attn_layer_apply(lp, x, cfg, run, angles=None, causal=False)
        return x, None

    x, _ = jax.lax.scan(_remat(layer, run), x, params["encoder"])
    return L.rmsnorm_apply(params["enc_norm"], x, cfg.norm_eps)


def forward_encdec(params: dict, batch: dict, cfg: ModelConfig,
                   run: RunConfig, last_only: bool = False):
    tokens = batch["tokens"]
    B, S = tokens.shape
    enc_out = encode(params, batch["frames"], cfg, run)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    ang = _angles(cfg, positions)
    x = L.embed_apply(params["embed"], tokens, _adtype(cfg),
                       onehot=cfg.tie_embeddings)

    def layer(x, lp):
        xn = L.rmsnorm_apply(lp["ln1"], x, cfg.norm_eps)
        x = x + L.attention_apply(lp["attn"], xn, cfg, angles=ang, causal=True,
                                  impl=run.attention_impl,
                                  chunk=run.attention_chunk)
        xn = L.rmsnorm_apply(lp["ln_x"], x, cfg.norm_eps)
        # cross-attention: KV from encoder output (no rope)
        kx = (enc_out @ lp["xattn"]["wk"]).reshape(
            B, enc_out.shape[1], cfg.n_kv_heads, cfg.resolved_head_dim)
        vx = (enc_out @ lp["xattn"]["wv"]).reshape(
            B, enc_out.shape[1], cfg.n_kv_heads, cfg.resolved_head_dim)
        q = (xn @ lp["xattn"]["wq"]).reshape(
            B, S, cfg.n_heads, cfg.resolved_head_dim)
        o = L.chunked_attention(q, kx, vx, causal=False,
                                chunk=run.attention_chunk)
        x = x + (o.reshape(B, S, -1) @ lp["xattn"]["wo"])
        xn = L.rmsnorm_apply(lp["ln2"], x, cfg.norm_eps)
        x = x + L.mlp_apply(lp["mlp"], xn, cfg)
        return x, None

    x, _ = jax.lax.scan(_remat(layer, run), x, params["decoder"])
    if last_only:
        x = x[:, -1:]
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    return L.unembed_apply(params["embed"], x), {}


def init_cache_encdec(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    KH, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    F = cfg.encoder_seq
    return {
        "k": jnp.zeros((cfg.n_layers, batch, max_seq, KH, Dh), _adtype(cfg)),
        "v": jnp.zeros((cfg.n_layers, batch, max_seq, KH, Dh), _adtype(cfg)),
        # precomputed cross-attention KV (from the encoder pass)
        "xk": jnp.zeros((cfg.n_layers, batch, F, KH, Dh), _adtype(cfg)),
        "xv": jnp.zeros((cfg.n_layers, batch, F, KH, Dh), _adtype(cfg)),
    }


def precompute_cross_kv(params: dict, enc_out: jax.Array, cfg: ModelConfig):
    """enc_out: [B, F, D] -> (xk, xv): [Ldec, B, F, KH, Dh]."""
    B, F, _ = enc_out.shape
    KH, Dh = cfg.n_kv_heads, cfg.resolved_head_dim

    def one(lp):
        xk = (enc_out @ lp["xattn"]["wk"]).reshape(B, F, KH, Dh)
        xv = (enc_out @ lp["xattn"]["wv"]).reshape(B, F, KH, Dh)
        return xk, xv

    return jax.vmap(one)(params["decoder"])


def decode_encdec(params: dict, cache: dict, batch: dict, cfg: ModelConfig,
                  run: RunConfig):
    tokens = batch["tokens"]
    seq_lens = batch["seq_lens"]
    B = tokens.shape[0]
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    x = L.embed_apply(params["embed"], tokens, _adtype(cfg),
                       onehot=cfg.tie_embeddings)
    positions = seq_lens[:, None].astype(jnp.int32)
    ang = _angles(cfg, positions)
    F = cache["xk"].shape[2]

    wpos = _active_pos(batch, cache["k"].shape[2])

    def layer(x, inputs):
        lp, kc, vc, xk, xv = inputs
        xn = L.rmsnorm_apply(lp["ln1"], x, cfg.norm_eps)
        q, k, v = L.attention_qkv(lp["attn"], xn, cfg, ang)
        kc = _cache_insert(kc, k, wpos)
        vc = _cache_insert(vc, v, wpos)
        o = _dec_attn(run)(q[:, 0], kc, vc, seq_lens[:, None] + 1)
        x = x + (o.reshape(B, 1, H * Dh) @ lp["attn"]["wo"])
        # cross attention against precomputed encoder KV
        xn = L.rmsnorm_apply(lp["ln_x"], x, cfg.norm_eps)
        qx = (xn @ lp["xattn"]["wq"]).reshape(B, 1, H, Dh)
        ox = L.decode_attention(qx[:, 0], xk, xv, F)
        x = x + (ox.reshape(B, 1, H * Dh) @ lp["xattn"]["wo"])
        xn = L.rmsnorm_apply(lp["ln2"], x, cfg.norm_eps)
        x = x + L.mlp_apply(lp["mlp"], xn, cfg)
        return x, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(
        layer, x, (params["decoder"], cache["k"], cache["v"],
                   cache["xk"], cache["xv"]))
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed_apply(params["embed"], x)[:, 0]
    return logits, {"k": k_new, "v": v_new, "xk": cache["xk"],
                    "xv": cache["xv"]}


# ===========================================================================
# Family dispatch
# ===========================================================================

_FAMILY = {
    "dense": (init_dense, forward_dense, init_cache_dense, decode_dense),
    "moe": (init_dense, forward_dense, init_cache_dense, decode_dense),
    "ssm": (init_ssm, forward_ssm, init_cache_ssm, decode_ssm),
    "hybrid": (init_hybrid, forward_hybrid, init_cache_hybrid, decode_hybrid),
    "encdec": (init_encdec, forward_encdec, init_cache_encdec, decode_encdec),
}


def init(key: jax.Array, cfg: ModelConfig) -> dict:
    if cfg.layer_pattern:
        assert cfg.family in ("dense", "moe"), cfg.family
        assert cfg.n_layers % len(cfg.layer_pattern) == 0, cfg.n_layers
    return _FAMILY[cfg.family][0](key, cfg)


def forward(params: dict, batch: dict, cfg: ModelConfig, run: RunConfig,
            last_only: bool = False):
    return _FAMILY[cfg.family][1](params, batch, cfg, run, last_only=last_only)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    return _FAMILY[cfg.family][2](cfg, batch, max_seq)


def decode_step(params: dict, cache: dict, batch: dict, cfg: ModelConfig,
                run: RunConfig, counters: bool = False):
    """(logits, new_cache), and with ``counters`` the step's MoE counters
    (``moe.COUNTERS``; None for a family without routed experts)."""
    fn = _FAMILY[cfg.family][3]
    if fn is decode_dense:
        return fn(params, cache, batch, cfg, run, counters=counters)
    out = fn(params, cache, batch, cfg, run)
    return out + (None,) if counters else out


# ===========================================================================
# Serving prefill: forward pass that also materializes the decode cache
# ===========================================================================

def _last_hidden(x: jax.Array, batch: dict) -> jax.Array:
    """Select the true last-prompt position per sequence.

    Prompts may be right-padded to a bucket length; `last_index` [B] gives
    each sequence's final real position (default: the last column)."""
    idx = batch.get("last_index")
    if idx is None:
        return x[:, -1:]
    B = x.shape[0]
    return x[jnp.arange(B), idx][:, None]


def _pad_seq(arr: jax.Array, max_seq: int, axis: int = 2) -> jax.Array:
    """Pad the seq axis of collected KV [L, B, S, KH, Dh] out to max_seq."""
    pad = [(0, 0)] * arr.ndim
    pad[axis] = (0, max_seq - arr.shape[axis])
    return jnp.pad(arr, pad)


def _ring(kv: jax.Array, n_real: jax.Array, W: int) -> jax.Array:
    """A prompt's sliding-layer KV [n, B, S, KH, Dh] laid out as the decode
    ring [n, B, W, KH, Dh]: entry r holds the latest real position p with
    p = r mod W (zeros where no position is), as decode writes them."""
    S = kv.shape[2]
    r = jnp.arange(W)
    pos = r[None, :] + W * ((n_real[:, None] - 1 - r[None, :]) // W)  # [B,W]
    ok = pos >= 0
    idx = jnp.clip(pos, 0, S - 1)[None, :, :, None, None]
    ring = jnp.take_along_axis(kv, idx, axis=2)
    return jnp.where(ok[None, :, :, None, None], ring, 0).astype(kv.dtype)


def prefill_dense_with_cache(params: dict, batch: dict, cfg: ModelConfig,
                             run: RunConfig, max_seq: int,
                             counters: bool = False):
    """Returns (last_logits [B, V], cache) — dense/moe families — and with
    ``counters`` the MoE counters (i32[4] summed over layers, or None).
    Positions past ``last_index`` are padding: experts never see them, and
    causal attention keeps them out of every real position."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = L.embed_apply(params["embed"], tokens, _adtype(cfg),
                      onehot=cfg.tie_embeddings)
    ropes = _ropes(cfg, positions)
    kinds = cfg.layer_period
    P = len(kinds)
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    last = batch.get("last_index")
    n_real = (jnp.full((B,), S, jnp.int32) if last is None
              else last.astype(jnp.int32) + 1)

    layers, experts = _split_experts(params["layers"])

    def layer(x, lp, kind, index):
        ang, scale = ropes[kind]
        xn = L.rmsnorm_apply(lp["ln1"], x, cfg.norm_eps)
        q, k, v = L.attention_qkv(lp["attn"], xn, cfg, ang, scale)
        if run.attention_impl == "naive":
            o = L.naive_attention(q, k, v, causal=True,
                                  window=_window(cfg, kind))
        else:
            o = L.chunked_attention(q, k, v, causal=True,
                                    chunk=run.attention_chunk,
                                    window=_window(cfg, kind))
        x = x + (o.reshape(B, S, H * Dh) @ lp["attn"]["wo"])
        xn = L.rmsnorm_apply(lp["ln2"], x, cfg.norm_eps)
        if experts is not None:
            valid = jnp.arange(S)[None, :] < n_real[:, None]
            h2, count = X.moe_dropless({**lp["mlp"], **experts}, xn, cfg,
                                       valid, layer=index)
        else:
            h2, count = L.mlp_apply(lp["mlp"], xn, cfg), None
        return x + h2, (k.astype(_adtype(cfg)), v.astype(_adtype(cfg))), count

    def period(x, inputs):
        lps, g = inputs
        kvs = {kind: [] for kind in kinds}
        counts = []
        for j, kind in enumerate(kinds):
            x, kv, count = layer(x, _member(lps, j, P), kind, g * P + j)
            kvs[kind].append(kv)
            counts.append(count)
        if P == 1:
            kvs = {kinds[0]: kvs[kinds[0]][0]}
        else:
            kvs = {kind: tuple(jnp.stack(t) for t in zip(*kv))
                   for kind, kv in kvs.items()}
        return x, (kvs, _sum_counters(counts))

    x, (kvs, counts) = jax.lax.scan(
        period, x, (_periods(layers, P), jnp.arange(cfg.n_layers // P)))
    if P > 1:   # [periods, per, B, S, ...] -> [layers of the kind, B, S, ...]
        kvs = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), kvs)
    x = L.rmsnorm_apply(params["final_norm"], _last_hidden(x, batch),
                        cfg.norm_eps)
    logits = L.unembed_apply(params["embed"], x)[:, 0]
    ks, vs = kvs["full"]
    cache = {"k": _pad_seq(ks, max_seq), "v": _pad_seq(vs, max_seq)}
    if "sliding" in kvs:
        W = ring_size(cfg, max_seq)
        cache["k_win"] = _ring(kvs["sliding"][0], n_real, W)
        cache["v_win"] = _ring(kvs["sliding"][1], n_real, W)
    if not counters:
        return logits, cache
    return logits, cache, None if counts is None else counts.sum(axis=0)


def prefill_ssm_with_cache(params: dict, batch: dict, cfg: ModelConfig,
                           run: RunConfig, max_seq: int):
    tokens = batch["tokens"]
    x = L.embed_apply(params["embed"], tokens, _adtype(cfg),
                      onehot=cfg.tie_embeddings)
    impl = "pallas" if run.attention_impl == "pallas" else "chunked"

    def layer(x, lp):
        h, (ssm_state, conv_state) = M.mamba2_apply(
            lp["mixer"], L.rmsnorm_apply(lp["ln"], x, cfg.norm_eps), cfg,
            impl=impl, return_state=True)
        return x + h, (ssm_state, conv_state)

    x, (ssm_s, conv_s) = jax.lax.scan(layer, x, params["layers"])
    x = L.rmsnorm_apply(params["final_norm"], _last_hidden(x, batch),
                        cfg.norm_eps)
    logits = L.unembed_apply(params["embed"], x)[:, 0]
    return logits, {"ssm": ssm_s, "conv": conv_s.astype(_adtype(cfg))}


def prefill_hybrid_with_cache(params: dict, batch: dict, cfg: ModelConfig,
                              run: RunConfig, max_seq: int):
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = L.embed_apply(params["embed"], tokens, _adtype(cfg),
                      onehot=cfg.tie_embeddings)
    ang = _angles(cfg, positions)
    shared = params["shared"]
    n_groups, period = _hybrid_groups(cfg)
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    impl = "pallas" if run.attention_impl == "pallas" else "chunked"

    def mamba_layer(x, lp):
        h, st = M.mamba2_apply(
            lp["mixer"], L.rmsnorm_apply(lp["ln"], x, cfg.norm_eps), cfg,
            impl=impl, return_state=True)
        return x + h, st

    def group(x, glp):
        x, (ssm_g, conv_g) = jax.lax.scan(mamba_layer, x, glp)
        xn = L.rmsnorm_apply(shared["ln1"], x, cfg.norm_eps)
        q, k, v = L.attention_qkv(shared["attn"], xn, cfg, ang)
        o = L.chunked_attention(q, k, v, causal=True,
                                chunk=run.attention_chunk)
        x = x + (o.reshape(B, S, H * Dh) @ shared["attn"]["wo"])
        xn = L.rmsnorm_apply(shared["ln2"], x, cfg.norm_eps)
        x = x + L.mlp_apply(shared["mlp"], xn, cfg)
        return x, (ssm_g, conv_g, k.astype(_adtype(cfg)),
                   v.astype(_adtype(cfg)))

    x, (ssm_g, conv_g, ks, vs) = jax.lax.scan(
        group, x, _group_params(params, cfg))
    x = L.rmsnorm_apply(params["final_norm"], _last_hidden(x, batch),
                        cfg.norm_eps)
    logits = L.unembed_apply(params["embed"], x)[:, 0]
    cache = {
        "ssm": ssm_g.reshape((cfg.n_layers,) + ssm_g.shape[2:]),
        "conv": conv_g.reshape((cfg.n_layers,) + conv_g.shape[2:]).astype(
            _adtype(cfg)),
        "k": _pad_seq(ks, max_seq),
        "v": _pad_seq(vs, max_seq),
    }
    return logits, cache


def prefill_encdec_with_cache(params: dict, batch: dict, cfg: ModelConfig,
                              run: RunConfig, max_seq: int):
    tokens = batch["tokens"]
    B, S = tokens.shape
    enc_out = encode(params, batch["frames"], cfg, run)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    ang = _angles(cfg, positions)
    x = L.embed_apply(params["embed"], tokens, _adtype(cfg),
                      onehot=cfg.tie_embeddings)
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    F = enc_out.shape[1]

    def layer(x, lp):
        xn = L.rmsnorm_apply(lp["ln1"], x, cfg.norm_eps)
        q, k, v = L.attention_qkv(lp["attn"], xn, cfg, ang)
        o = L.chunked_attention(q, k, v, causal=True,
                                chunk=run.attention_chunk)
        x = x + (o.reshape(B, S, H * Dh) @ lp["attn"]["wo"])
        xn = L.rmsnorm_apply(lp["ln_x"], x, cfg.norm_eps)
        kx = (enc_out @ lp["xattn"]["wk"]).reshape(B, F, KH, Dh)
        vx = (enc_out @ lp["xattn"]["wv"]).reshape(B, F, KH, Dh)
        qx = (xn @ lp["xattn"]["wq"]).reshape(B, S, H, Dh)
        ox = L.chunked_attention(qx, kx, vx, causal=False,
                                 chunk=run.attention_chunk)
        x = x + (ox.reshape(B, S, H * Dh) @ lp["xattn"]["wo"])
        xn = L.rmsnorm_apply(lp["ln2"], x, cfg.norm_eps)
        x = x + L.mlp_apply(lp["mlp"], xn, cfg)
        return x, (k.astype(_adtype(cfg)), v.astype(_adtype(cfg)),
                   kx.astype(_adtype(cfg)), vx.astype(_adtype(cfg)))

    x, (ks, vs, xks, xvs) = jax.lax.scan(layer, x, params["decoder"])
    x = L.rmsnorm_apply(params["final_norm"], _last_hidden(x, batch),
                        cfg.norm_eps)
    logits = L.unembed_apply(params["embed"], x)[:, 0]
    cache = {"k": _pad_seq(ks, max_seq), "v": _pad_seq(vs, max_seq),
             "xk": xks, "xv": xvs}
    return logits, cache


_PREFILL_CACHE = {
    "dense": prefill_dense_with_cache,
    "moe": prefill_dense_with_cache,
    "ssm": prefill_ssm_with_cache,
    "hybrid": prefill_hybrid_with_cache,
    "encdec": prefill_encdec_with_cache,
}


def prefill_with_cache(params: dict, batch: dict, cfg: ModelConfig,
                       run: RunConfig, max_seq: int, counters: bool = False):
    """(last_logits [B, V], decode-ready cache) for every family, and with
    ``counters`` the MoE counters as :func:`decode_step` gives them."""
    fn = _PREFILL_CACHE[cfg.family]
    if fn is prefill_dense_with_cache:
        return fn(params, batch, cfg, run, max_seq, counters=counters)
    out = fn(params, batch, cfg, run, max_seq)
    return out + (None,) if counters else out
