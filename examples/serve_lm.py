"""Serve a small LM with continuously-batched requests — as a v2 DSL app.

The serving loop is a real DataX application (migrated from the raw-Operator
v1 style): a request driver feeds a ``requests`` stream, an SDK-style engine
analytics unit owns the continuous-batching loop (submit -> tick -> emit),
and responses land on a ``responses`` stream any consumer can reuse (§3).

The request stream is **keyed by session** (``.key_by("session")``): every
session's requests reach the same engine instance in order, and the KV slot
table lives in the stream's platform database — exactly the per-session
state locality that lets ``.scaled(instances=N)`` shard sessions across N
engines without forking their state (this example keeps one engine so the
jit compile is paid once).

The engine reads its model from its stream configuration: ``arch`` names a
published architecture (``""`` keeps the small built-in preset), ``layers``
cuts its depth (0 keeps it), ``seed`` makes its random weights.
``chip_smoke.py`` deploys this app with qwen3-14b at full width.

Run:  PYTHONPATH=src python examples/serve_lm.py --requests 12 --slots 4
"""
import argparse
import dataclasses
import functools
import time

import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.configs.base import RunConfig
from repro.core import (App, ConfigSchema, FieldSpec, StreamSchema, connect,
                        drain, sdk_entrypoint)

REQUEST = StreamSchema.of(
    request_id=FieldSpec("str"), session=FieldSpec("str"),
    prompt=FieldSpec("ndarray", shape=(-1,), dtype="int32"),
    max_new=FieldSpec("int"))
RESPONSE = StreamSchema.of(
    request_id=FieldSpec("str"), session=FieldSpec("str"),
    prompt_len=FieldSpec("int"), tokens=FieldSpec("int"),
    ttft_ms=FieldSpec("float"))

app = App("serve-lm")


@app.driver(emits=REQUEST)
def request_gen(ctx, requests=12, sessions=3, vocab=4096, prompt_min=4,
                prompt_max=24, max_new=16, seed=0):
    rng = np.random.default_rng(seed)

    def gen():
        for i in range(requests):
            if not ctx.running:
                return
            plen = int(rng.integers(prompt_min, prompt_max))
            prompt = rng.integers(1, vocab, plen, dtype=np.int32)
            yield {"request_id": f"req-{i:03d}",
                   "session": f"sess-{i % sessions}",
                   "prompt": prompt,
                   "max_new": max_new}
    return gen()


def make_model(arch: str = "", layers: int = 0, attention: str = "naive",
               seed: int = 0):
    """``(cfg, run, params)`` of the served model: the small built-in preset
    when ``arch`` is empty, else ``arch`` at its published widths;
    ``layers > 0`` cuts the depth.  Weights are random, from ``seed``."""
    import jax

    from repro import models

    if arch:
        cfg = get_config(arch)
    else:
        cfg = dataclasses.replace(
            get_smoke_config("qwen3-14b"), n_layers=4, d_model=128,
            n_heads=4, n_kv_heads=2, d_ff=512, vocab=4096, head_dim=32)
    if layers:
        cfg = dataclasses.replace(cfg, n_layers=layers)
    run = RunConfig(attention_impl=attention, attention_chunk=256,
                    remat="none")
    # jitted, so each weight is drawn straight into its own dtype (eager
    # init would hold a float32 copy of the largest matrices on the device)
    init = jax.jit(functools.partial(models.init, cfg=cfg))
    return cfg, run, init(jax.random.PRNGKey(seed))


@app.analytics_unit(expects=(REQUEST,), emits=RESPONSE, stateful=True,
                    config=ConfigSchema.of(slots=("int", 4),
                                           max_new=("int", 16),
                                           max_seq=("int", 256),
                                           arch=("str", ""),
                                           layers=("int", 0),
                                           attention=("str", "naive"),
                                           seed=("int", 0)))
@sdk_entrypoint
def lm_engine(dx):
    """SDK-style engine: owns its loop, three-method SDK + platform db."""
    from repro.serve import ServeEngine

    conf = dx.get_configuration()
    cfg, run, params = make_model(conf["arch"], conf["layers"],
                                  conf["attention"], conf["seed"])
    # the KV slot table lives in the stream's platform database: an engine
    # restart — or a session re-homed by keyed rebalance — recovers its map
    engine = ServeEngine(cfg, run, params, n_slots=conf["slots"],
                         max_seq=conf["max_seq"], db=dx.db)
    sessions: dict[str, str] = {}
    while dx.running:
        item = dx.next(timeout=0.02)
        if item is not None:
            _, payload = item
            sessions[payload["request_id"]] = payload["session"]
            engine.submit(payload["request_id"],
                          [int(t) for t in payload["prompt"]],
                          max_new_tokens=min(payload["max_new"],
                                             conf["max_new"]))
        if not engine.batcher.idle:
            for req in engine.tick():
                dx.emit({"request_id": req.request_id,
                         "session": sessions.pop(req.request_id, ""),
                         "prompt_len": len(req.prompt),
                         "tokens": len(req.generated),
                         "ttft_ms": (req.first_token_at - req.arrived) * 1e3})


def build_app(requests=12, slots=4, max_new=16, *, prompt=(4, 24),
              **model) -> App:
    """Wire the serving topology (request driver -> session-keyed engine ->
    tapped responses) and return the app — also the entry point
    ``datax check`` discovers.  ``prompt`` is the [min, max) prompt length;
    ``model`` holds engine settings (``arch``, ``layers``, ``max_seq``,
    ``attention``, ``seed``), defaulting to the small preset."""
    vocab = get_config(model["arch"]).vocab if model.get("arch") else 4096
    reqs = app.sense("requests", request_gen, requests=requests, vocab=vocab,
                     prompt_min=prompt[0], prompt_max=prompt[1],
                     max_new=max_new)
    responses = (reqs.key_by("session")
                 .via(lm_engine, name="responses", slots=slots,
                      max_new=max_new, fixed_instances=1, **model))
    responses.tap()   # promised to external consumers (§3 reuse)
    return app


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()

    build_app(requests=args.requests, slots=args.slots,
              max_new=args.max_new)

    t0 = time.perf_counter()
    with connect() as op:
        app.deploy(op, start_sensors=False)
        sub = op.subscribe("responses", maxsize=args.requests + 8)
        op.start_pending_sensors()
        done = drain(sub, args.requests, timeout=600)
        dt = time.perf_counter() - t0
        toks = sum(m.payload["tokens"] for m in done)
        print(f"served {len(done)} requests / {toks} tokens in {dt:.2f}s "
              f"({toks/dt:.0f} tok/s) with {args.slots} KV slots")
        for m in sorted(done, key=lambda m: m.payload["request_id"])[:5]:
            p = m.payload
            print(f"  {p['request_id']} ({p['session']}): "
                  f"{p['prompt_len']}-token prompt -> {p['tokens']} tokens, "
                  f"ttft {p['ttft_ms']:.0f} ms")
        group = (op.executor.instances_of("responses")[0]
                 .sidecar.metrics()["groups"]["requests"])
        db = op.store.get("au-responses")
        print(f"request delivery: {group['policy']} on {group.get('key')!r} "
              f"({group['delivered']} delivered); KV slot table "
              f"{db.tables()} lives in platform db {db.name!r}")


if __name__ == "__main__":
    main()
