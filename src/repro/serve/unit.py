"""A session-keyed DataX analytics unit that serves language-model requests
through a :class:`ServeEngine`.

    requests (driver) --key_by("session")--> serve unit --> answers (tap)

The unit takes every waiting request before each engine tick and emits two
messages per request on its output stream: ``first`` once its first token
exists and ``done`` with every generated id.  The engine's slot table lives
in the stream's platform database.  Each tick leaves a record in
:attr:`SessionUnit.steps`:

* ``t`` — host clock after the tick;
* ``prefill`` — prompt lengths of the requests that got their first token;
* ``active`` — sequences the tick's decode step advanced;
* ``attended`` (= ``attended_full``) — key/value positions each full
  attention layer read in that step, summed over the decoded sequences;
* ``attended_window`` — the same for each sliding layer: its ring holds at
  most the window, so a sequence reads min(length, window) there;
* ``decode_moe``, ``prefill_moe`` — the routed-expert counters of the
  decode step and of each prefill (``moe.COUNTERS`` order), where the
  model has experts.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from repro.models import transformer as T

from .engine import ServeEngine


class SessionUnit:
    """Deploys one serving unit.  ``make_engine(db)`` builds the engine
    inside the unit (where it owns the device); ``warm(engine)`` compiles
    what the traffic will need before the unit reports ready."""

    def __init__(self, make_engine, *, warm=None, output: str = "answers",
                 app_name: str = "serve"):
        self.make_engine = make_engine
        self.warm = warm
        self.output = output
        self.app_name = app_name
        self.engine: ServeEngine | None = None
        self.steps: list = []

    def deploy(self, op, source, *, maxsize: int = 4096):
        """Deploy onto ``op`` with ``source(ctx)`` — an iterator of request
        payloads (request_id, session, prompt, max_new) — as the driver;
        returns the subscription to the answers once the engine is warm."""
        from repro.core import App, FieldSpec, StreamSchema, sdk_entrypoint

        request = StreamSchema.of(
            request_id=FieldSpec("str"), session=FieldSpec("str"),
            prompt=FieldSpec("ndarray", shape=(-1,), dtype="int32"),
            max_new=FieldSpec("int"))
        answer = StreamSchema.of(
            request_id=FieldSpec("str"), session=FieldSpec("str"),
            kind=FieldSpec("str"),
            tokens=FieldSpec("ndarray", shape=(-1,), dtype="int32"))
        app = App(self.app_name)
        ready = threading.Event()
        unit = self

        @app.driver(emits=request)
        def clients(ctx):
            return source(ctx)

        @app.analytics_unit(expects=(request,), emits=answer, stateful=True)
        @sdk_entrypoint
        def serve(dx):
            engine = unit.make_engine(dx.db)
            unit.engine = engine
            if unit.warm is not None:
                unit.warm(engine)
            ready.set()
            unit._loop(dx, engine)

        (app.sense("requests", clients).key_by("session")
            .via(serve, name=self.output, fixed_instances=1)
            .tap())
        app.deploy(op, start_sensors=False)
        sub = op.subscribe(self.output, maxsize=maxsize)
        instance = op.executor.instances_of(self.output)[0]
        while not ready.wait(0.1):
            if not instance.alive():
                raise RuntimeError(f"serving unit died in set-up:\n"
                                   f"{instance.crash_info}")
        return sub

    def _loop(self, dx, engine: ServeEngine) -> None:
        sessions: dict = {}
        announced: set = set()
        has_ring = "k_win" in engine.cache
        ring = T.ring_size(engine.cfg, engine.max_seq) if has_ring else 0
        while dx.running:
            item = dx.next(timeout=0.02 if engine.batcher.idle else 0.0)
            while item is not None:                  # every waiting request
                p = item[1]
                sessions[p["request_id"]] = p["session"]
                engine.submit(p["request_id"], list(p["prompt"]),
                              max_new_tokens=p["max_new"])
                item = dx.next(timeout=0.0)
            if engine.batcher.idle:
                continue
            made = engine.metrics["tokens_generated"]
            finished = engine.tick()
            live = list(engine.batcher.live.values())
            new = [r for r in live if r.prefill_done
                   and r.request_id not in announced]
            fresh = {r.request_id for r in new}
            lens = np.asarray([int(engine.seq_lens[r.slot]) for r in live
                               if r.prefill_done
                               and r.request_id not in fresh], np.int64)
            record = {"t": time.monotonic(),
                      "prefill": [len(r.prompt) for r in new],
                      "active": engine.metrics["tokens_generated"] - made,
                      "attended": int(lens.sum())}
            if has_ring:
                record["attended_full"] = record["attended"]
                record["attended_window"] = int(np.minimum(lens, ring).sum())
            counted = engine.step_counters
            if counted["decode"] is not None or counted["prefill"]:
                record["decode_moe"] = (None if counted["decode"] is None
                                        else counted["decode"].tolist())
                record["prefill_moe"] = [c.tolist()
                                         for c in counted["prefill"]]
            self.steps.append(record)
            for r in new:
                announced.add(r.request_id)
                dx.emit({"request_id": r.request_id,
                         "session": sessions[r.request_id], "kind": "first",
                         "tokens": np.asarray(r.generated[:1], np.int32)})
            for r in finished:
                announced.discard(r.request_id)
                dx.emit({"request_id": r.request_id,
                         "session": sessions.pop(r.request_id),
                         "kind": "done",
                         "tokens": np.asarray(r.generated, np.int32)})
            engine.batcher.completed.clear()
