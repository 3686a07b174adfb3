"""AdamW + LR schedule + global-norm clipping + gradient compression.

Hand-rolled (no optax dependency) so optimizer-state sharding (ZeRO-1) and
compression hooks stay explicit:

* m/v in f32 regardless of param dtype; optional f32 master weights.
* warmup + cosine schedule.
* gradient compression with error feedback (bf16 cast or int8 EF-SGD-style):
  the distributed-optimization trick — on a real pod the quantized tensor is
  what crosses the wire; here the quantize->dequantize runs inside the step
  so convergence behaviour is faithfully reproduced.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig


# ---------------------------------------------------------------------------
# Schedule
# ---------------------------------------------------------------------------

def lr_schedule(step: jax.Array, run: RunConfig,
                total_steps: int = 100_000) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / max(run.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - run.warmup_steps)
                    / max(total_steps - run.warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return run.learning_rate * warm * (0.1 + 0.9 * cos)


# ---------------------------------------------------------------------------
# Compression (error feedback)
# ---------------------------------------------------------------------------

def compress_grad(g: jax.Array, err: jax.Array | None, mode: str):
    """Returns (decompressed grad, new error buffer)."""
    if mode == "none" or g.dtype == jnp.int32:
        return g, err
    if mode == "bf16":
        gq = g.astype(jnp.bfloat16).astype(jnp.float32)
        return gq, err
    if mode == "int8_ef":
        g32 = g.astype(jnp.float32) + (err if err is not None else 0.0)
        scale = jnp.maximum(jnp.max(jnp.abs(g32)), 1e-12) / 127.0
        q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
        deq = q.astype(jnp.float32) * scale
        return deq, (g32 - deq)
    raise ValueError(f"unknown compression mode {mode!r}")


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------

def init_opt_state(params: Any, run: RunConfig,
                   master_weights: bool = False) -> dict:
    zeros32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    state = {
        "m": jax.tree.map(zeros32, params),
        "v": jax.tree.map(zeros32, params),
        "count": jnp.zeros((), jnp.int32),
    }
    if run.grad_compression == "int8_ef":
        state["err"] = jax.tree.map(zeros32, params)
    if master_weights:
        state["master"] = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    return state


def global_norm(tree: Any) -> jax.Array:
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32)))
              for g in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def adamw_update(grads: Any, params: Any, state: dict, run: RunConfig,
                 total_steps: int = 100_000):
    """One AdamW step.  Returns (new_params, new_state, metrics)."""
    count = state["count"] + 1
    lr = lr_schedule(count, run, total_steps)

    # compression with error feedback
    if run.grad_compression != "none":
        errs = state.get("err")
        if errs is not None:
            gq = jax.tree.map(
                lambda g, e: compress_grad(g, e, run.grad_compression),
                grads, errs)
            grads = jax.tree.map(lambda t: t[0], gq,
                                 is_leaf=lambda t: isinstance(t, tuple))
            new_err = jax.tree.map(lambda t: t[1], gq,
                                   is_leaf=lambda t: isinstance(t, tuple))
        else:
            grads = jax.tree.map(
                lambda g: compress_grad(g, None, run.grad_compression)[0],
                grads)
            new_err = None
    else:
        new_err = state.get("err")

    # global-norm clip (f32)
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, run.grad_clip / jnp.maximum(gnorm, 1e-12))
    b1, b2, eps = run.beta1, run.beta2, 1e-8
    bc1 = 1.0 - b1 ** count.astype(jnp.float32)
    bc2 = 1.0 - b2 ** count.astype(jnp.float32)

    masters = state.get("master")

    def upd(p, g, m, v, master=None):
        g = g.astype(jnp.float32) * clip
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mhat = m / bc1
        vhat = v / bc2
        base = (master if master is not None else p.astype(jnp.float32))
        step = lr * (mhat / (jnp.sqrt(vhat) + eps)
                     + run.weight_decay * base)
        new_master = base - step
        return new_master.astype(p.dtype), m, v, new_master

    if masters is not None:
        out = jax.tree.map(upd, params, grads, state["m"], state["v"], masters)
    else:
        out = jax.tree.map(lambda p, g, m, v: upd(p, g, m, v),
                           params, grads, state["m"], state["v"])

    new_params = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda t: isinstance(t, tuple))
    new_m = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda t: isinstance(t, tuple))
    new_v = jax.tree.map(lambda t: t[2], out,
                         is_leaf=lambda t: isinstance(t, tuple))
    new_state = {"m": new_m, "v": new_v, "count": count}
    if masters is not None:
        new_state["master"] = jax.tree.map(
            lambda t: t[3], out, is_leaf=lambda t: isinstance(t, tuple))
    if new_err is not None:
        new_state["err"] = new_err
    metrics = {"lr": lr, "grad_norm": gnorm}
    return new_params, new_state, metrics
