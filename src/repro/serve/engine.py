"""ServeEngine — continuous-batching inference as a DataX application.

  requests (sensor) -> admission/batcher (host AU) ->
      {prefill, decode} (DEVICE AUs, pjit on the mesh) -> responses (stream)

Engine tick:
  1. plan_tick() — finish EOS/len-capped requests, free slots, admit waiters;
  2. prefill each admitted request (prompt bucketed to limit compilations),
     scatter its KV/state into the slot pool, emit its first token;
  3. one lockstep decode step over ALL live slots (per-slot positions —
     sequences at different lengths decode together);
  4. publish finished responses.

The slot table persists in a DataX database (StateStore), so an engine
restart recovers its session map — the paper's state-management claim
exercised by the serving path.
"""
from __future__ import annotations

import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs.base import ModelConfig, RunConfig
from repro.core.spans import span
from repro.core.state import Database
from repro.distributed.act_sharding import activation_mesh
from repro.launch.mesh import make_host_mesh
from repro.models import moe as X
from repro.models import transformer as T

from .batcher import ContinuousBatcher, Request
from .kvcache import SlotAllocator


def _bucket(n: int, buckets=(32, 64, 128, 256, 512, 1024, 2048)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return -(-n // 2048) * 2048


class ServeEngine:
    def __init__(self, cfg: ModelConfig, run: RunConfig, params,
                 *, n_slots: int = 8, max_seq: int = 512, mesh=None,
                 db: Database | None = None, eos_id: int | None = None):
        self.cfg = cfg
        self.run = run
        self.mesh = mesh or make_host_mesh()
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.batcher = ContinuousBatcher(n_slots)
        self.slots = SlotAllocator(n_slots, db=db)
        self.params = params
        # routed experts report per-call counters (moe.COUNTERS), fetched
        # with the call's tokens or logits in the same transfer
        self._counted = cfg.moe is not None and cfg.family in ("dense",
                                                                "moe")
        self.cache = models.init_cache(cfg, n_slots, max_seq)
        self.seq_lens = np.zeros((n_slots,), np.int32)
        self.last_token = np.zeros((n_slots,), np.int32)
        # *_s: host seconds of the engine's spans (tick_s, device_wait_s:
        # both syncs), and slot_wait_s: admission minus arrival, summed
        self.metrics = {"ticks": 0, "prefills": 0, "decode_steps": 0,
                        "tokens_generated": 0, "tick_s": 0.0,
                        "device_wait_s": 0.0, "slot_wait_s": 0.0}
        if self._counted:
            self.metrics.update(dict.fromkeys(X.COUNTERS, 0))
        # this tick's MoE counters: the decode step's, and each prefill's
        self.step_counters: dict = {"decode": None, "prefill": []}
        self._decode = self._build_decode()
        self._prefill_cache: dict[int, Any] = {}

    # ------------------------------------------------------------------ jits
    def _build_decode(self):
        cfg, run, mesh = self.cfg, self.run, self.mesh
        counted = self._counted

        def step(params, cache, batch):
            with activation_mesh(mesh):
                if counted:
                    logits, cache, counts = models.decode_step(
                        params, cache, batch, cfg, run, counters=True)
                else:
                    logits, cache = models.decode_step(params, cache, batch,
                                                       cfg, run)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if counted:     # one array, one transfer: tokens then counters
                tok = jnp.concatenate([tok, counts])
            return tok, cache

        return jax.jit(step, donate_argnums=(1,))

    def _get_prefill(self, plen: int):
        if plen not in self._prefill_cache:
            cfg, run, mesh, max_seq = self.cfg, self.run, self.mesh, self.max_seq

            def prefill(params, batch):
                with activation_mesh(mesh):
                    return T.prefill_with_cache(params, batch, cfg, run,
                                                max_seq,
                                                counters=self._counted)

            self._prefill_cache[plen] = jax.jit(prefill)
        return self._prefill_cache[plen]

    @functools.cached_property
    def _insert_fns(self):
        """Per-leaf jitted slot inserts (donated pool)."""
        def insert_kv(pool, piece, slot, plen):
            # pool [L, B, S, ...]; piece [L, 1, Sp, ...]
            return jax.lax.dynamic_update_slice(
                pool, piece.astype(pool.dtype),
                (0, slot, 0) + (0,) * (pool.ndim - 3))

        def insert_state(pool, piece, slot, plen):
            # pool [L, B, ...]; piece [L, 1, ...]
            return jax.lax.dynamic_update_slice(
                pool, piece.astype(pool.dtype),
                (0, slot) + (0,) * (pool.ndim - 2))

        return (jax.jit(insert_kv, donate_argnums=(0,)),
                jax.jit(insert_state, donate_argnums=(0,)))

    # -------------------------------------------------------------- lifecycle
    def submit(self, request_id, prompt: list[int],
               max_new_tokens: int = 32) -> None:
        self.batcher.submit(Request(request_id=request_id, prompt=list(prompt),
                                    max_new_tokens=max_new_tokens,
                                    eos_id=self.eos_id))

    def _do_prefill(self, req: Request) -> None:
        plen = len(req.prompt)
        if self.cfg.family in ("ssm", "hybrid"):
            # recurrent state is taken at the end of the prompt — padding
            # would roll garbage into it -> exact-length prefill
            bucket = plen
        else:
            # causal attention ignores right-padding (masked by seq_lens),
            # and experts never see it (prefill's routing validity mask)
            bucket = min(_bucket(plen), self.max_seq)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = req.prompt
        batch = {"tokens": jnp.asarray(toks),
                 "last_index": jnp.asarray([plen - 1], jnp.int32)}
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (1, self.cfg.encoder_seq, self.cfg.d_model),
                jnp.dtype(self.cfg.activation_dtype))
        out = self._get_prefill(bucket)(self.params, batch)
        logits, small = out[:2]
        # NOTE: right-padded prompts attend causally, so positions < plen are
        # unaffected by the padding; states for SSM families are taken at the
        # bucket end — we therefore bucket SSM prompts exactly.
        slot = self.slots.alloc(req.request_id)
        req.slot = slot
        insert_kv, insert_state = self._insert_fns
        with span("datax.engine.insert"):
            for name, pool in self.cache.items():
                piece = small[name]
                if name in ("k", "v", "k_win", "v_win", "xk", "xv"):
                    self.cache[name] = insert_kv(pool, piece, slot, plen)
                else:
                    self.cache[name] = insert_state(pool, piece, slot, plen)
        with span("datax.engine.prefill_wait", self.metrics, "device_wait_s"):
            if self._counted:
                host, counts = jax.device_get((logits, out[2]))
                self._count(counts)
                self.step_counters["prefill"].append(counts)
            else:
                host = np.asarray(logits)
            req.prefill_logits = host[0]
        first = int(req.prefill_logits.argmax())
        req.generated.append(first)
        req.prefill_done = True
        req.first_token_at = time.monotonic()
        self.seq_lens[slot] = plen
        self.last_token[slot] = first
        self.metrics["prefills"] += 1

    def _do_decode(self, live: list[Request]) -> None:
        active = np.zeros((self.n_slots,), bool)
        for req in live:
            active[req.slot] = True
        batch = {
            "tokens": jnp.asarray(self.last_token[:, None]),
            "seq_lens": jnp.asarray(self.seq_lens),
            "active": jnp.asarray(active),
        }
        next_tok, self.cache = self._decode(self.params, self.cache, batch)
        with span("datax.engine.decode_wait", self.metrics, "device_wait_s"):
            next_tok = np.asarray(next_tok)
        if self._counted:
            next_tok, counts = next_tok[:self.n_slots], next_tok[self.n_slots:]
            self._count(counts)
            self.step_counters["decode"] = counts
        for req in live:
            s = req.slot
            self.seq_lens[s] += 1
            tok = int(next_tok[s])
            req.generated.append(tok)
            self.last_token[s] = tok
            self.metrics["tokens_generated"] += 1
        self.metrics["decode_steps"] += 1

    def _count(self, counts) -> None:
        for key, n in zip(X.COUNTERS, counts):
            self.metrics[key] += int(n)

    def tick(self) -> list[Request]:
        """One engine iteration; returns requests finished this tick."""
        self.step_counters = {"decode": None, "prefill": []}
        with span("datax.engine.tick", self.metrics, "tick_s"):
            with span("datax.engine.plan"):
                plan = self.batcher.plan_tick(self.slots.n_free)
                for req in plan.finished:
                    self.slots.free(req.request_id)
            for req in plan.admit:
                self.metrics["slot_wait_s"] += req.admitted_at - req.arrived
                with span("datax.engine.prefill", request_id=req.request_id):
                    self._do_prefill(req)
            if plan.decode:
                with span("datax.engine.decode"):
                    self._do_decode(plan.decode)
        self.metrics["ticks"] += 1
        return plan.finished

    def run_until_idle(self, max_ticks: int = 10_000) -> list[Request]:
        done: list[Request] = []
        for _ in range(max_ticks):
            done.extend(self.tick())
            if self.batcher.idle:
                break
        done.extend(self.tick())  # flush final finishes
        return done
