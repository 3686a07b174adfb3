"""Mixture-of-Experts FFN: top-k router + GShard-style grouped dispatch.

Tokens are partitioned into fixed-size GROUPS (GShard/Switch "expert group
size"), each with its own capacity C = ceil(S·k/E·factor).  This keeps the
dispatch tensors at [G, S, E, C] with S ≈ 2k instead of a single global
[T, E, C] whose capacity grows with T — the global form is O(T²) memory and
exploded at prefill scale (T = 1M ⇒ C = 256k).  The group dim carries the
batch sharding, so routing is local to each data shard and the expert
einsums lower to expert-parallel collectives when experts are sharded.

    dispatch [G,S,E,C] (bf16 0/1) · x [G,S,D] -> [G,E,C,D]   (a2a/scatter)
    expert FFN on [E, G·C, D]                                 (local compute)
    combine  [G,S,E,C] (bf16, gate-scaled) · y -> out         (a2a/gather)

Aux losses (Switch load-balance + router z-loss) are averaged over groups.
Training takes this path (:func:`moe_apply`).

Serving takes the dropless path (:func:`moe_dropless`): every valid token
reaches all of its top-k experts.  Token-expert pairs are sorted by expert
and each expert's rows go through one grouped matmul (the Pallas
``expert_gmm`` on a TPU that holds the whole layer, ``lax.ragged_dot``
elsewhere).  A validity mask sends pad tokens and inactive decode slots
nowhere, so a prompt padded to its bucket routes exactly as unpadded.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.act_sharding import constrain, whole_on_one_tpu
from repro.kernels import ops


def init_moe(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    assert cfg.moe is not None
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    params = {
        "router": (jax.random.normal(k1, (d, e)) * s_in).astype(jnp.float32),
        "w_up": (jax.random.normal(k3, (e, d, f)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k4, (e, f, d)) * s_out).astype(dtype),
    }
    if cfg.act == "silu":
        params["w_gate"] = (jax.random.normal(k2, (e, d, f)) * s_in).astype(dtype)
    return params


GROUP_SIZE = 2048  # default GShard expert-group size


def moe_group_shape(n_tokens: int, group_size: int = GROUP_SIZE) -> tuple[int, int]:
    """(n_groups, group_size) with group_size | n_tokens."""
    s = min(group_size, n_tokens)
    while n_tokens % s:
        s //= 2
    return n_tokens // s, max(s, 1)


def moe_capacity(group_size: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    cap = int(math.ceil(group_size * m.top_k / m.num_experts
                        * m.capacity_factor))
    return max(8, -(-cap // 8) * 8)


def moe_apply(params: dict, x: jax.Array, cfg: ModelConfig,
              group_size: int = GROUP_SIZE) -> tuple:
    """x: [B, S, D] -> (out [B, S, D], aux: dict of scalar losses)."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    E, K = m.num_experts, m.top_k
    Gp, Sg = moe_group_shape(T, group_size)
    C = moe_capacity(Sg, cfg)
    xg = x.reshape(Gp, Sg, D)
    xg = constrain(xg, "batch", None, None)

    logits = (xg.astype(jnp.float32) @ params["router"])        # [G, S, E]
    probs = jax.nn.softmax(logits, axis=-1)

    # --- top-k selection with per-group capacity positions -------------------
    # Lean integer/boolean routing: every intermediate is bool/i32 and the
    # only [G,S,E,C]-sized tensors are the bf16 dispatch/combine masks
    # themselves.  (The textbook f32 one-hot formulation materializes
    # [G,S,K,C] and [G,S,K,E] float tensors — measured 4x the HBM traffic
    # of the experts; EXPERIMENTS.md §Perf cell A.)
    topk_probs, topk_idx = jax.lax.top_k(probs, K)              # [G, S, K]
    topk_probs = topk_probs / jnp.maximum(
        topk_probs.sum(axis=-1, keepdims=True), 1e-9)

    sel = (topk_idx[..., None] ==
           jnp.arange(E, dtype=jnp.int32))                      # [G,S,K,E] bool
    # priority: round-major (1st choices first), token order within a round
    flat = sel.transpose(0, 2, 1, 3).reshape(Gp, K * Sg, E)
    pos_flat = jnp.cumsum(flat.astype(jnp.int32), axis=1) - flat
    pos = pos_flat.reshape(Gp, K, Sg, E).transpose(0, 2, 1, 3)  # [G,S,K,E] i32
    within = (pos < C) & sel                                    # bool
    kept = within.any(-1)                                       # [G, S, K] bool
    # per-(token, expert) slot: E-reduction of the K selection tensors
    pos_e = jnp.where(within, pos, 0).sum(2)                    # [G, S, E] i32
    sel_e = within.any(2)                                       # [G, S, E] bool
    gate_e = jnp.where(
        sel_e, jnp.einsum("gske,gsk->gse", within.astype(jnp.float32),
                          topk_probs), 0.0)                     # [G, S, E] f32

    c_iota = jnp.arange(C, dtype=jnp.int32)
    slot_hit = sel_e[..., None] & (pos_e[..., None] == c_iota)  # [G,S,E,C] bool
    dispatch = slot_hit.astype(x.dtype)                         # bf16 0/1
    combine = jnp.where(slot_hit, gate_e[..., None], 0.0).astype(x.dtype)
    dispatch = constrain(dispatch, "batch", None, "expert", None)
    combine = constrain(combine, "batch", None, "expert", None)

    # --- expert computation ----------------------------------------------------
    xin = jnp.einsum("gsec,gsd->gecd", dispatch, xg)            # [G, E, C, D]
    xin = constrain(xin, "batch", "expert", None, None)
    if cfg.act == "silu":
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xin, params["w_gate"])) \
            * jnp.einsum("gecd,edf->gecf", xin, params["w_up"])
    else:
        h = jax.nn.gelu(jnp.einsum("gecd,edf->gecf", xin, params["w_up"]))
    h = constrain(h, "batch", "expert", None, "hidden")
    y = jnp.einsum("gecf,efd->gecd", h, params["w_down"])       # [G, E, C, D]
    y = constrain(y, "batch", "expert", None, None)
    out = jnp.einsum("gsec,gecd->gsd", combine, y)              # [G, S, D]

    # --- aux losses --------------------------------------------------------------
    me = probs.mean(axis=1)                                     # [G, E]
    ce = sel[:, :, 0, :].astype(jnp.float32).mean(axis=1)       # [G, E]
    lb = E * jnp.sum(me * ce, axis=-1).mean() * m.load_balance_loss
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * m.router_z_loss
    dropped = 1.0 - kept.astype(jnp.float32).mean()
    aux = {"moe_load_balance": lb, "moe_z_loss": z,
           "moe_drop_fraction": dropped}
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# dropless serving path
# ---------------------------------------------------------------------------

# the counters one routed layer reports, in the order moe_dropless returns
COUNTERS = ("moe_expert_tokens_max", "moe_routed_pairs", "moe_experts_hit",
            "moe_dropped")
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _grouped_matmul():
    """``(lhs, w [L, E, k, n], sizes, layer) -> f32``: the Pallas kernel,
    reading layer ``layer`` of the stack in place, where one TPU holds it;
    elsewhere (a mesh, no TPU) ``lax.ragged_dot`` on the sliced layer."""
    if whole_on_one_tpu():
        return lambda lhs, w, sizes, layer: ops.expert_gmm(
            lhs, w, sizes, layer, interpret=False)
    return lambda lhs, w, sizes, layer: jax.lax.ragged_dot(
        lhs, w[layer], sizes, preferred_element_type=jnp.float32)


def moe_dropless(params: dict, x: jax.Array, cfg: ModelConfig,
                 valid: jax.Array | None = None, layer=None) -> tuple:
    """x: [B, S, D], valid: [B, S] bool (None: every token) ->
    (out [B, S, D], counters i32[4] in :data:`COUNTERS` order).

    No capacity: each valid token's top-k pairs all reach their experts,
    and invalid tokens route nowhere and come out zero.  With ``layer``,
    the expert weights in ``params`` are the whole stack [L, E, ...] and
    this layer's are read where they lie."""
    m = cfg.moe
    B, S, D = x.shape
    T, E, K = B * S, m.num_experts, m.top_k
    xt = x.reshape(T, D)
    logits = xt.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
    gates = gates / jnp.maximum(gates.sum(axis=-1, keepdims=True), 1e-9)
    pair_ok = (jnp.ones((T, K), bool) if valid is None else
               jnp.broadcast_to(valid.reshape(T, 1), (T, K))).reshape(-1)
    key = jnp.where(pair_ok, experts.reshape(-1), E)    # invalid sort last
    order = jnp.argsort(key, stable=True)               # pairs by expert
    sizes = jnp.zeros((E + 1,), jnp.int32).at[key].add(1)[:E]
    xs = xt[order // K]                                  # [T*K, D]
    if layer is None:
        params, layer = {k: (v[None] if k in EXPERT_WEIGHTS else v)
                         for k, v in params.items()}, 0
    grouped = _grouped_matmul()

    def gmm(lhs, name):
        return grouped(lhs, params[name], sizes, layer)
    if cfg.act == "silu":
        h = jax.nn.silu(gmm(xs, "w_gate")) * gmm(xs, "w_up")
    else:
        h = jax.nn.gelu(gmm(xs, "w_up"))
    y = gmm(h.astype(x.dtype), "w_down")                 # f32 [T*K, D]
    routed = sizes.sum()
    # rows past the routed ones belong to no expert: the kernel leaves them
    # unwritten
    y = jnp.where((jnp.arange(T * K) < routed)[:, None], y, 0.0)
    unsort = jnp.zeros_like(order).at[order].set(jnp.arange(T * K))
    y = y[unsort].reshape(T, K, D)
    out = (y * gates[..., None]).sum(axis=1).astype(x.dtype)
    counters = jnp.stack([sizes.max(), routed, (sizes > 0).sum(),
                          pair_ok.sum() - routed]).astype(jnp.int32)
    return out.reshape(B, S, D), counters
