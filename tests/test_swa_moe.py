"""Mixed sliding-window / full attention with routed experts (Mellum2's
block) on the serving path, at the smoke size on the CPU, against the
plain float32 reference (``models/ref_swa_moe.py``) on seeded weights:
logits, not sampled tokens, are compared."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_config, get_smoke_config
from repro.configs.base import RunConfig
from repro.kernels import ops
from repro.models import layers as L
from repro.models import moe as X
from repro.models import ref_swa_moe as R
from repro.models import transformer as T
from repro.serve import ServeEngine

ARCH = "mellum2-12b-a2.5b"
KEY = jax.random.PRNGKey(0)
RUN = RunConfig(attention_impl="chunked", attention_chunk=16, remat="none")
# float32 params and activations: the program then computes what the
# reference computes, so the logits agree to float32 rounding
TOL = 2e-5


def _cfg(**kw):
    return dataclasses.replace(get_smoke_config(ARCH),
                               param_dtype="float32",
                               activation_dtype="float32", **kw)


def _close(got, want):
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(got, want, atol=TOL * max(scale, 1.0))


def _prefill(params, cfg, prompt, bucket, max_seq):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(prompt)] = prompt
    batch = {"tokens": jnp.asarray(toks),
             "last_index": jnp.asarray([len(prompt) - 1], jnp.int32)}
    return T.prefill_with_cache(params, batch, cfg, RUN, max_seq,
                                counters=True)


def test_config_keeps_the_published_pattern():
    cfg = get_config(ARCH)
    assert cfg.layer_pattern == ("sliding",) * 3 + ("full",)
    assert cfg.n_layers % len(cfg.layer_pattern) == 0
    assert (cfg.sliding_window, cfg.moe.num_experts, cfg.moe.top_k,
            cfg.d_ff) == (1024, 64, 8, 896)
    smoke = get_smoke_config(ARCH)
    assert smoke.layer_pattern == cfg.layer_pattern
    assert (smoke.sliding_window, smoke.moe.num_experts,
            smoke.moe.top_k) == (8, 8, 2)


def test_yarn_frequencies_match_the_closed_form():
    """YaRN (arXiv:2309.00071 §3.2): dimension i turns r_i = L / λ_i times
    over the original context L, λ_i = 2π θ^(2i/d); h(θ_i) = (1 - γ) θ_i / s
    + γ θ_i with γ the ramp of r_i between β_slow and β_fast, the ramp's
    ends taken at whole dimensions as Hugging Face takes them."""
    cfg = get_config(ARCH)
    y, d, theta = cfg.yarn, cfg.resolved_head_dim, cfg.rope_theta
    i = np.arange(d // 2, dtype=np.float64)
    base = theta ** (-2 * i / d)
    # the dimension at which r = beta, solved from L θ^(-2i/d) / 2π = beta
    at = [d * math.log(y.original_max_position / (2 * math.pi * b))
          / (2 * math.log(theta)) for b in (y.beta_fast, y.beta_slow)]
    lo, hi = max(math.floor(at[0]), 0), min(math.ceil(at[1]), d - 1)
    gamma = 1 - np.clip((i - lo) / (hi - lo), 0, 1)
    want = (1 - gamma) * base / y.factor + gamma * base
    got = L.yarn_inv_freq(d, theta, y)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(R.inv_freq(cfg, "full"), want, rtol=1e-12)
    # the fastest dimensions are kept, the slowest interpolated by 16
    assert got[0] == pytest.approx(1.0)
    assert got[-1] == pytest.approx(base[-1] / 16, rel=1e-5)
    assert y.attention_factor == pytest.approx(0.1 * math.log(16) + 1)


@pytest.mark.parametrize("window", [0, 1, 5, 8, 40])
def test_chunked_window_matches_naive(window):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (2, 37, 4, 8))
    k = jax.random.normal(k2, (2, 37, 2, 8))
    v = jax.random.normal(k3, (2, 37, 2, 8))
    want = L.naive_attention(q, k, v, causal=True, window=window)
    got = L.chunked_attention(q, k, v, causal=True, chunk=8, window=window)
    np.testing.assert_allclose(got, want, atol=1e-5)
    if window:          # query i sees exactly keys i - window < j <= i
        i = 20
        lo = max(0, i - window + 1)
        one = L.naive_attention(q[:, i:i + 1], k[:, lo:i + 1],
                                v[:, lo:i + 1], causal=False)
        np.testing.assert_allclose(want[:, i:i + 1], one, atol=1e-5)


def test_prefill_then_decode_past_the_window_matches_reference():
    """Prefill a 13-token prompt (bucketed to 32) into slot 1 of a pool,
    then decode 4 x the window + 8 tokens: the rings wrap five times.
    Every step's logits are the reference's at that position."""
    cfg = _cfg()
    params = models.init(KEY, cfg)
    prompt = np.random.default_rng(0).integers(1, cfg.vocab, 13)
    steps = 4 * cfg.sliding_window + 8
    lg, piece, _ = _prefill(params, cfg, prompt, 32, 64)
    cache = models.init_cache(cfg, 2, 64)
    cache = {k: v.at[:, 1:2].set(piece[k]) for k, v in cache.items()}
    seq, logits = list(prompt), [np.asarray(lg[0])]
    step = jax.jit(lambda p, c, b: models.decode_step(p, c, b, cfg, RUN))
    for _ in range(steps):
        seq.append(int(np.argmax(logits[-1])))
        batch = {"tokens": jnp.asarray([[0], [seq[-1]]], jnp.int32),
                 "seq_lens": jnp.asarray([0, len(seq) - 1], jnp.int32),
                 "active": jnp.asarray([False, True])}
        lg, cache = step(params, cache, batch)
        logits.append(np.asarray(lg[1]))
    want = np.asarray(R.forward(params, np.asarray(seq), cfg))
    _close(np.stack(logits), want[len(prompt) - 1:])
    # the inactive slot's rows were never written
    assert not np.any(np.asarray(cache["k_win"][:, 0]))
    assert not np.any(np.asarray(cache["k"][:, 0]))


def test_engine_serves_the_reference_past_the_window():
    """Continuous batching through ServeEngine: ragged joins, bucketed
    prefill, slot reuse; each request's prefill logits and greedy tokens
    are the reference's."""
    cfg = _cfg()
    params = models.init(KEY, cfg)
    eng = ServeEngine(cfg, RUN, params, n_slots=2, max_seq=64)
    rng = np.random.default_rng(1)
    prompts = {f"r{i}": list(rng.integers(1, cfg.vocab, n))
               for i, n in enumerate((5, 19, 11))}
    for rid, p in prompts.items():
        eng.submit(rid, p, max_new_tokens=3 * cfg.sliding_window)
    done = {r.request_id: r for r in eng.run_until_idle()}
    assert len(done) == 3
    for rid, p in prompts.items():
        r = done[rid]
        seq = p + r.generated[:-1]
        want = np.asarray(R.forward(params, np.asarray(seq), cfg))
        _close(r.prefill_logits, want[len(p) - 1])
        assert r.generated == [int(t) for t in
                               want[len(p) - 1:].argmax(axis=-1)]
    assert eng.metrics["moe_dropped"] == 0
    assert eng.metrics["moe_routed_pairs"] > 0


def test_bucketed_prefill_equals_exact_length():
    """Pad tokens route nowhere: a prompt padded to its bucket gives the
    logits, caches and expert counts of the unpadded prompt."""
    cfg = _cfg()
    params = models.init(KEY, cfg)
    prompt = np.random.default_rng(2).integers(1, cfg.vocab, 21)
    lg_b, c_b, n_b = _prefill(params, cfg, prompt, 32, 64)
    lg_e, c_e, n_e = _prefill(params, cfg, prompt, 21, 64)
    _close(np.asarray(lg_b), np.asarray(lg_e))
    for name in ("k", "v"):        # rows past the prompt are never read
        _close(np.asarray(c_b[name][:, :, :21]),
               np.asarray(c_e[name][:, :, :21]))
    for name in ("k_win", "v_win"):
        _close(np.asarray(c_b[name]), np.asarray(c_e[name]))
    np.testing.assert_array_equal(np.asarray(n_b), np.asarray(n_e))
    K = cfg.moe.top_k
    assert int(n_b[1]) == 21 * K * cfg.n_layers      # routed pairs
    assert int(n_b[3]) == 0                          # none dropped


def test_prefill_ring_holds_each_position_at_its_slot():
    """The ring piece prefill returns holds position p at p mod W, the
    latest W positions of the prompt, and zeros where none is."""
    cfg = _cfg()
    params = models.init(KEY, cfg)
    W = cfg.sliding_window
    for n in (3, W, 2 * W + 3):
        prompt = np.random.default_rng(n).integers(1, cfg.vocab, n)
        _, piece, _ = _prefill(params, cfg, prompt, 32, 64)
        ring = np.asarray(piece["k_win"][:, 0])     # [n_sliding, W, KH, Dh]
        for r in range(n, W):
            assert not np.any(ring[:, r])
        kv = _sliding_keys(params, cfg, prompt)
        for r in range(min(n, W)):
            p = r + W * ((n - 1 - r) // W)
            np.testing.assert_allclose(ring[:, r], kv[:, p], rtol=1e-5,
                                       atol=1e-5)


def _sliding_keys(params, cfg, prompt):
    """Keys of every sliding layer at every prompt position, [n_sliding,
    S, KH, Dh], as prefill computes them (before the ring)."""
    captured = []
    ring = T._ring

    def keep(kv, n_real, W):
        captured.append(np.asarray(kv[:, 0]))
        return ring(kv, n_real, W)
    T._ring = keep
    try:
        T.prefill_with_cache(params, {"tokens": jnp.asarray(prompt[None])},
                             cfg, RUN, 64)
    finally:
        T._ring = ring
    return captured[0]


def test_all_tokens_to_one_expert_equals_reference():
    """Dropless: with the router sending every token to expert 0 first,
    nothing is dropped (the capacity path would keep 1.25 x its share) and
    the layer is the reference's."""
    cfg = _cfg()
    params = models.init(KEY, cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    router = lp["mlp"]["router"].at[:, 0].add(50.0)
    mlp = {**lp["mlp"], "router": router}
    # positive activations: every token's score for expert 0 is the largest
    x = jnp.abs(jax.random.normal(KEY, (2, 24, cfg.d_model))) + 0.5
    got, counts = X.moe_dropless(mlp, x, cfg)
    T_ = 2 * 24
    assert int(counts[0]) == T_                       # expert 0 holds all
    assert int(counts[1]) == T_ * cfg.moe.top_k
    assert int(counts[3]) == 0
    with jax.default_matmul_precision("highest"):
        want = _experts_raw(mlp, x, cfg)
    _close(np.asarray(got), np.asarray(want))
    capped, aux = X.moe_apply(mlp, x, cfg)
    assert float(aux["moe_drop_fraction"]) > 0       # capacity would drop


def _experts_raw(mlp, x, cfg):
    """The reference's expert sum on x itself (no input norm)."""
    m = cfg.moe
    p = jax.nn.softmax(x @ mlp["router"], axis=-1)
    top, idx = jax.lax.top_k(p, m.top_k)
    top = top / top.sum(axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(m.num_experts):
        g = jnp.sum(jnp.where(idx == e, top, 0.0), axis=-1)
        y = jax.nn.silu(x @ mlp["w_gate"][e]) * (x @ mlp["w_up"][e])
        out = out + g[..., None] * (y @ mlp["w_down"][e])
    return out


def test_invalid_tokens_route_nowhere():
    cfg = _cfg()
    params = models.init(KEY, cfg)
    mlp = jax.tree.map(lambda a: a[0], params["layers"])["mlp"]
    x = jax.random.normal(KEY, (1, 10, cfg.d_model))
    valid = jnp.arange(10)[None] < 6
    out, counts = X.moe_dropless(mlp, x, cfg, valid)
    assert not np.any(np.asarray(out[0, 6:]))
    assert int(counts[1]) == 6 * cfg.moe.top_k
    head, _ = X.moe_dropless(mlp, x[:, :6], cfg)
    _close(np.asarray(out[:, :6]), np.asarray(head))


@pytest.mark.parametrize("sizes", [[60, 0, 100, 50], [0, 0, 256, 0],
                                   [1, 1, 1, 1]])
def test_expert_gmm_reads_each_layer_of_the_stack(sizes):
    """The Pallas grouped matmul (interpret mode) against lax.ragged_dot
    on the sliced layer, over rows sorted by group with a tail in none."""
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.normal(size=(256, 256)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(3, 4, 256, 384)), jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    rows = sum(sizes)
    for layer in range(3):
        got = ops.expert_gmm(lhs, rhs, gs, jnp.int32(layer), interpret=True)
        want = jax.lax.ragged_dot(lhs, rhs[layer], gs,
                                  preferred_element_type=jnp.float32)
        np.testing.assert_allclose(np.asarray(got[:rows]),
                                   np.asarray(want[:rows]), rtol=1e-5,
                                   atol=1e-3)


def test_decode_counters_count_the_active_slots():
    cfg = _cfg()
    params = models.init(KEY, cfg)
    cache = models.init_cache(cfg, 4, 32)
    batch = {"tokens": jnp.ones((4, 1), jnp.int32),
             "seq_lens": jnp.asarray([3, 0, 9, 20], jnp.int32),
             "active": jnp.asarray([True, False, True, True])}
    _, _, counts = models.decode_step(params, cache, batch, cfg, RUN,
                                      counters=True)
    assert int(counts[1]) == 3 * cfg.moe.top_k * cfg.n_layers
    assert int(counts[3]) == 0
    assert 0 < int(counts[2]) <= cfg.moe.num_experts * cfg.n_layers
