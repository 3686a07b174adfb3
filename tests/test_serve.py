"""Serving: continuous batching correctness + slot reuse + persistence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_smoke_config
from repro.configs.base import RunConfig
from repro.core.state import StateStore
from repro.serve import CacheFullError, ServeEngine, SlotAllocator
from repro.serve.batcher import ContinuousBatcher, Request

RUN = RunConfig(attention_impl="naive", remat="none", attention_chunk=16)
KEY = jax.random.PRNGKey(0)


def _f32(cfg):
    return dataclasses.replace(cfg, param_dtype="float32",
                               activation_dtype="float32")


@pytest.mark.parametrize("arch", ["qwen3-32b", "mamba2-370m", "zamba2-2.7b",
                                  "grok-1-314b", "whisper-large-v3"])
def test_engine_matches_full_forward_greedy(arch):
    """All five families: continuous batching (ragged joins, slot reuse)
    must emit exactly the greedy continuation of a full forward pass."""
    cfg = _f32(get_smoke_config(arch))
    params = models.init(KEY, cfg)
    eng = ServeEngine(cfg, RUN, params, n_slots=2, max_seq=64)
    prompts = {f"r{i}": list(np.random.default_rng(i).integers(
        1, cfg.vocab, 4 + 2 * i)) for i in range(3)}
    for rid, p in prompts.items():
        eng.submit(rid, p, max_new_tokens=5)
    done = eng.run_until_idle()
    assert len(done) == 3

    def fwd_batch(toks):
        b = {"tokens": jnp.asarray([toks])}
        if cfg.family == "encdec":
            b["frames"] = jnp.zeros((1, cfg.encoder_seq, cfg.d_model))
        return b

    def ref_logits_full_forward(toks):
        logits, _ = models.forward(params, fwd_batch(toks), cfg, RUN)
        return np.asarray(logits[0, -1])

    def ref_logits_decode(state, toks):
        """Token-by-token decode reference (B=1) — required for MoE: the
        full forward is the training path, whose capacity routing is
        group-size dependent (one group of len(toks) tokens, drops
        possible), while serving routes dropless, one token at a time
        here and the prompt padded to its bucket in the engine."""
        cache, pos = state
        lg = None
        while pos < len(toks):
            batch = {"tokens": jnp.asarray([[toks[pos]]]),
                     "seq_lens": jnp.asarray([pos], jnp.int32)}
            lg, cache = models.decode_step(params, cache, batch, cfg, RUN)
            pos += 1
        state[0], state[1] = cache, pos
        return np.asarray(lg[0])

    for rid, prompt in prompts.items():
        req = next(r for r in done if r.request_id == rid)
        gen = req.generated
        toks = list(prompt)
        dec_state = [models.init_cache(cfg, 1, 64), 0]
        for step in range(5):
            if cfg.family == "moe":
                ref = ref_logits_decode(dec_state, toks)
            else:
                ref = ref_logits_full_forward(toks)
            if step == 0:     # the engine's prefill logits, bucketed prompt
                np.testing.assert_allclose(req.prefill_logits, ref,
                                           rtol=1e-4, atol=1e-5)
            nxt = int(np.argmax(ref))
            assert gen[step] == nxt, (rid, step, gen)
            toks.append(nxt)


def test_slot_reuse_continuous_batching():
    cfg = _f32(get_smoke_config("qwen3-32b"))
    params = models.init(KEY, cfg)
    eng = ServeEngine(cfg, RUN, params, n_slots=2, max_seq=32)
    for i in range(5):  # 5 requests through 2 slots
        eng.submit(f"r{i}", [1 + i, 2, 3], max_new_tokens=3)
    done = eng.run_until_idle()
    assert len(done) == 5
    assert eng.slots.n_free == 2            # all slots returned
    assert all(len(r.generated) == 3 for r in done)


def test_slot_allocator_exhaustion_and_persistence():
    store = StateStore()
    db = store.create("serving")
    alloc = SlotAllocator(2, db=db)
    alloc.alloc("a")
    alloc.alloc("b")
    with pytest.raises(CacheFullError):
        alloc.alloc("c")
    alloc.free("a")
    alloc.alloc("c")
    # restart: session map recovered from the platform database
    alloc2 = SlotAllocator(2, db=db)
    assert alloc2.n_free == 0
    assert alloc2.slot_of("b") is not None and alloc2.slot_of("c") is not None


def test_batcher_policy():
    b = ContinuousBatcher(n_slots=2, max_prefill_per_tick=1)
    for i in range(3):
        b.submit(Request(request_id=i, prompt=[1], max_new_tokens=1))
    t1 = b.plan_tick(free_slots=2)
    assert len(t1.admit) == 1 and not t1.decode
    t1.admit[0].prefill_done = True
    t1.admit[0].generated = [5]             # done (max_new_tokens=1)
    t2 = b.plan_tick(free_slots=1)
    assert t1.admit[0] in t2.finished or len(t2.admit) == 1
    assert len(b.completed) >= 1
