"""Batched fused execution (tentpole PR 5) + fallback/poison bugfixes.

Contracts:
(a) bus: ``Subscription.next_batch`` pops up to max_n queued items in one
    lock acquisition — order preserved, group/keyed ``note_consumed``
    accounting intact, blocking only for the first item;
(b) sidecar: ``next_batch`` pulls a burst from ONE input subject and keeps
    batch-size metrics; executor drain-a-burst mode hands whole bursts to
    ``process_batch`` and degrades to the per-message path when shallow;
(c) fusion: batched execution is bit-identical to per-message execution and
    to the host chain (outputs, filter decisions, order) — property-tested
    across random chains, batch sizes and ragged tails; without jax the
    batch path cleanly degrades to the host chain;
(d) bugfix: one bad payload falls back for THAT message only (device mode
    stays live, ``device_fallbacks`` counted in sidecar metrics); a genuine
    trace failure still demotes permanently;
(e) bugfix: a poison message crashing an instance lands on the subject's
    ``lost`` stat, and reap -> ``depart()`` re-homes the crashed member's
    remaining mailbox backlog to group survivors.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import (AnalyticsUnitSpec, App, ConfigSchema, DriverSpec,
                        DSLError, Executor, FieldSpec, MessageBus, Operator,
                        OperatorError, SensorSpec, Sidecar, StreamSchema,
                        StreamSpec, connect, drain)
from repro.core import fusion
from repro.core.fusion import FusedStage, make_fused_logic
from repro.core.sdk import LogicContext

INT_SCHEMA = StreamSchema.of(value=FieldSpec("int"))
TEN = StreamSchema.device(x=((8, 8), "float32"))


# ---------------------------------------------------------------------------
# (a) bus: Subscription.next_batch
# ---------------------------------------------------------------------------

@pytest.fixture
def bus():
    b = MessageBus()
    b.register_subject("s", INT_SCHEMA)
    return b


def test_next_batch_orders_and_bounds(bus):
    tok = bus.issue_token("t", ["s"])
    sub = bus.subscribe("s", token=tok)
    for i in range(7):
        bus.publish("s", {"value": i}, token=tok)
    assert [m.payload["value"] for m in sub.next_batch(5, timeout=0)] == \
        [0, 1, 2, 3, 4]
    assert [m.payload["value"] for m in sub.next_batch(5, timeout=0)] == \
        [5, 6]
    assert sub.next_batch(5, timeout=0) == []
    assert sub.qsize() == 0


def test_next_batch_blocks_for_first_item_only(bus):
    tok = bus.issue_token("t", ["s"])
    sub = bus.subscribe("s", token=tok)
    t0 = time.monotonic()
    assert sub.next_batch(4, timeout=0.05) == []     # timeout, not hang
    assert time.monotonic() - t0 < 2.0
    bus.publish("s", {"value": 0}, token=tok)
    # one queued item -> a 1-message burst; no waiting for more to arrive
    assert [m.payload["value"] for m in sub.next_batch(4, timeout=5)] == [0]


def test_next_batch_stops_at_close_sentinel(bus):
    tok = bus.issue_token("t", ["s"])
    sub = bus.subscribe("s", token=tok)
    for i in range(2):
        bus.publish("s", {"value": i}, token=tok)
    sub.close()                                      # sentinel lands last
    assert [m.payload["value"] for m in sub.next_batch(10, timeout=0)] == \
        [0, 1]
    assert sub.next_batch(10, timeout=0.01) == []


def test_next_batch_decodes_wire_subscriptions(bus):
    tok = bus.issue_token("t", ["s"])
    sub = bus.subscribe("s", token=tok, wire=True)
    for i in range(3):
        bus.publish("s", {"value": i}, token=tok)
    batch = sub.next_batch(3, timeout=0)
    assert [m.payload["value"] for m in batch] == [0, 1, 2]
    assert all(m.subject == "s" for m in batch)


def test_next_batch_keeps_keyed_partition_accounting(bus):
    tok = bus.issue_token("t", ["s"])
    sub = bus.subscribe("s", token=tok, group="pool", key="value", name="m0")
    for i in range(6):
        bus.publish("s", {"value": i}, token=tok)
    before = bus.group_info("s", "pool")["partition_backlog"]
    assert sum(before.values()) == 6
    got = sub.next_batch(6, timeout=0)
    assert [m.payload["value"] for m in got] == list(range(6))
    # every popped item was note_consumed: exact backlog reaches zero
    assert bus.group_info("s", "pool")["partition_backlog"] == {}


# ---------------------------------------------------------------------------
# (b) sidecar burst pull + executor drain-a-burst mode
# ---------------------------------------------------------------------------

def test_sidecar_next_batch_records_burst_metrics():
    bus_ = MessageBus()
    bus_.register_subject("in", INT_SCHEMA)
    sc = Sidecar("i", bus_, inputs=("in",))
    tok = bus_.issue_token("pub", ["in"])
    for i in range(5):
        bus_.publish("in", {"value": i}, token=tok)
    stream, msgs = sc.next_batch(4, timeout=1)
    assert stream == "in" and [m.payload["value"] for m in msgs] == \
        [0, 1, 2, 3]
    stream, msgs = sc.next_batch(4, timeout=1)
    assert [m.payload["value"] for m in msgs] == [4]
    m = sc.metrics()
    assert (m["batches"], m["batch_msgs"], m["max_batch_seen"]) == (2, 5, 4)
    assert m["avg_batch"] == 2.5
    sc.close()
    bus_.close()


def test_pump_hands_bursts_to_process_batch():
    """A batching-capable process sees the queued backlog as bursts, with
    per-message emission order preserved (None = filtered)."""
    bus_ = MessageBus()
    bus_.register_subject("in", INT_SCHEMA)
    bus_.register_subject("out", INT_SCHEMA)
    ex = Executor(bus_)
    bursts = []

    def logic(ctx):
        def process(stream, payload):
            return {"value": payload["value"]}

        def process_batch(stream, payloads):
            bursts.append(len(payloads))
            return [None if p["value"] % 3 == 0 else {"value": p["value"]}
                    for p in payloads]
        process.process_batch = process_batch
        process.default_max_batch = 8
        return process

    tok = bus_.issue_token("pub", ["in"])
    out = bus_.subscribe("out", token=bus_.issue_token("ext", ["out"]))
    # preload the mailbox, then start the instance: the first pull sees a
    # deep mailbox and must drain it as bursts of <= 8
    sc = Sidecar("pre", bus_, inputs=("in",), output="out", group="w")
    for i in range(1, 20):
        bus_.publish("in", {"value": i}, token=tok)
    stop = threading.Event()
    t = threading.Thread(
        target=lambda: Executor._pump(logic(LogicContext({})), sc, stop,
                                      sink=False), daemon=True)
    t.start()
    expect = [i for i in range(1, 20) if i % 3 != 0]
    got = [m.payload["value"] for m in drain(out, len(expect), timeout=10)]
    stop.set()
    t.join(timeout=5)
    assert got == expect                      # order preserved, filters honored
    assert bursts and max(bursts) > 1         # batching actually engaged
    assert all(b <= 8 for b in bursts)
    sc.close()
    bus_.close()


# ---------------------------------------------------------------------------
# (c) batched == per-message == host chain (property-tested)
# ---------------------------------------------------------------------------

def _stage(kind, fn):
    if kind == "filter":
        factory = lambda ctx: (lambda s, p: p if fn(p) else None)  # noqa: E731
    else:
        factory = lambda ctx: (lambda s, p: fn(p))                 # noqa: E731
    return FusedStage(au_name=f"{kind}au", stream_name="st",
                      factory=factory, config={}, kind=kind, pure_fn=fn)


def _proc(stages, max_batch=None):
    return make_fused_logic(stages, None, max_batch=max_batch)(
        LogicContext({}))


_OPS = [
    ("map", lambda p: {"x": p["x"] * 2}),
    ("map", lambda p: {"x": p["x"] + 1}),
    ("map", lambda p: {"x": -p["x"]}),
    ("map", lambda p: {"x": p["x"], "s": p["x"].sum()}),
    ("filter", lambda p: p["x"][0] < 3),
    ("filter", lambda p: p["x"].sum() > -20),
]


def _assert_same_results(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        if ra is None or rb is None:
            assert ra is None and rb is None   # same filter decisions
            continue
        assert ra.keys() == rb.keys()
        for k in ra:
            va, vb = np.asarray(ra[k]), np.asarray(rb[k])
            assert va.dtype == vb.dtype, k
            assert np.array_equal(va, vb), k
        for k in ra:                            # scalar typing parity
            assert type(ra[k]) is type(rb[k]), k


try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except Exception:  # pragma: no cover - minimal-deps CI leg
    _HAS_HYPOTHESIS = False

if _HAS_HYPOTHESIS:
    _chains = st.lists(st.sampled_from(range(len(_OPS))), min_size=1,
                       max_size=4)

    @settings(max_examples=20, deadline=None)
    @given(_chains, st.integers(1, 9), st.integers(1, 4), st.booleans(),
           st.data())
    def test_batched_bit_identical_to_per_message(chain, batch, width,
                                                  ragged, data):
        """Across random chains, batch sizes and ragged tails: batched
        execution produces the same outputs, the same filter decisions, in
        the same order as per-message execution and as the host chain."""
        stages = [_stage(*_OPS[i]) for i in chain]
        payloads = []
        for b in range(batch):
            w = data.draw(st.integers(1, 4)) if ragged else width
            vals = data.draw(st.lists(st.integers(-5, 5), min_size=w,
                                      max_size=w))
            payloads.append({"x": np.asarray(vals, np.float32)})
        host = _proc([_stage(*_OPS[i]) for i in chain])
        expected = [host("s", dict(p)) for p in payloads]
        if fusion.jax_available():
            import os
            old = os.environ.get("DATAX_FUSION_JIT")
            os.environ["DATAX_FUSION_JIT"] = "always"
            try:
                dev_batched = _proc(stages, max_batch=batch)
                got = dev_batched.process_batch("s", [dict(p)
                                                      for p in payloads])
                _assert_same_results(got, expected)
                dev_single = _proc([_stage(*_OPS[i]) for i in chain])
                singles = [dev_single("s", dict(p)) for p in payloads]
                _assert_same_results(singles, expected)
            finally:
                if old is None:
                    del os.environ["DATAX_FUSION_JIT"]
                else:
                    os.environ["DATAX_FUSION_JIT"] = old
        else:
            got = host.process_batch("s", [dict(p) for p in payloads])
            _assert_same_results(got, expected)


def test_batch_path_degrades_to_host_chain_without_jax(monkeypatch):
    """The jax-free leg: process_batch exists, runs the host chain
    per message, and never claims a batched device burst."""
    monkeypatch.setattr(fusion, "_HAS_JAX", False)
    stages = [_stage(*_OPS[0]), _stage(*_OPS[4])]
    proc = _proc(stages, max_batch=8)
    payloads = [{"x": np.asarray([v, v], np.float32)} for v in range(5)]
    got = proc.process_batch("s", [dict(p) for p in payloads])
    expected = [proc("s", dict(p)) for p in payloads]
    _assert_same_results(got, expected)
    assert proc.stats["batched_bursts"] == 0
    assert proc.stats["device_fallbacks"] == 0


def test_batched_execution_end_to_end_ordered(monkeypatch):
    """Deployed fused unit with .scaled(max_batch=): outputs arrive in exact
    per-message order, bit-identical to the unfused bus run, and the sidecar
    shows bursts deeper than one message."""
    if not fusion.jax_available():
        pytest.skip("end-to-end batched device path needs jax")
    monkeypatch.setenv("DATAX_FUSION_JIT", "always")

    def build():
        app = App("batched")

        @app.driver(emits=TEN)
        def src(ctx, n=40):
            return ({"x": np.full((8, 8), float(i), np.float32)}
                    for i in range(n))

        (app.sense("raw", src, n=40)
            .map(lambda p: {"x": p["x"] * 2}, emits=TEN, device=True,
                 name="m1")
            .filter(lambda p: p["x"][0, 0] < 60.0, device=True, name="f1")
            .map(lambda p: {"x": p["x"] + 1}, emits=TEN, device=True,
                 name="exit")
            .scaled(max_batch=8))
        return app

    def run(fuse):
        with connect(start=False) as op:
            build().deploy(op, start_sensors=False, fuse=fuse)
            sub = op.subscribe("exit", maxsize=64)
            op.start_pending_sensors()
            out = [m.payload for m in drain(sub, 30, timeout=30)]
            handles = op.executor.instances_of("exit")
            metrics = handles[0].sidecar.metrics() if handles else {}
            return out, metrics

    fused, m = run(True)
    unfused, _ = run(False)
    assert len(fused) == len(unfused) == 30
    for pa, pb in zip(fused, unfused):       # exact order + bit-identity
        assert np.array_equal(pa["x"], pb["x"])
        assert np.asarray(pa["x"]).dtype == np.asarray(pb["x"]).dtype
    assert m["max_batch_seen"] > 1           # bursts actually happened
    assert m["batch_msgs"] == 40             # every input message, batched
    assert m["batched_bursts"] > 0           # the vmapped program really ran
    assert m["device_fallbacks"] == 0


# ---------------------------------------------------------------------------
# (d) bugfix: payload fallback is per-message, not a permanent demotion
# ---------------------------------------------------------------------------

def test_bad_payload_falls_back_per_message_keeps_device_mode(monkeypatch):
    if not fusion.jax_available():
        pytest.skip("device-mode fallback accounting needs jax")
    monkeypatch.delenv("DATAX_FUSION_JIT", raising=False)
    monkeypatch.setattr(fusion, "JIT_MODE", "always")
    proc = _proc([_stage("map", lambda p: {"x": p["x"] * 2})], max_batch=4)
    good = {"x": np.arange(4, dtype=np.float32)}
    assert np.array_equal(proc("s", dict(good))["x"], good["x"] * 2)
    # a single non-numeric payload: host chain for THIS message only
    assert proc("s", {"x": "bad"}) == {"x": "badbad"}
    assert proc.stats["device_fallbacks"] == 1
    # conversion failures that are NOT TypeError (an oversized python int
    # overflows jnp.asarray) are payload problems too — same fallback
    assert proc("s", {"x": 2 ** 80}) == {"x": 2 ** 81}
    assert proc.stats["device_fallbacks"] == 2
    # the device program is still live: the next burst runs batched
    out = proc.process_batch("s", [dict(good), dict(good)])
    assert proc.stats["batched_bursts"] == 1
    assert all(np.array_equal(o["x"], good["x"] * 2) for o in out)


def test_trace_failure_still_demotes_permanently(monkeypatch):
    if not fusion.jax_available():
        pytest.skip("trace-failure demotion needs jax")
    monkeypatch.delenv("DATAX_FUSION_JIT", raising=False)
    monkeypatch.setattr(fusion, "JIT_MODE", "always")
    # float(tracer) raises under jit: an impure stage, not a payload problem
    impure = lambda p: {"x": p["x"] * (2.0 if float(p["x"].sum()) >= 0  # noqa: E731
                                       else 1.0)}
    proc = _proc([_stage("map", impure)], max_batch=4)
    good = {"x": np.arange(4, dtype=np.float32)}
    assert np.array_equal(proc("s", dict(good))["x"], good["x"] * 2.0)
    out = proc.process_batch("s", [dict(good), dict(good)])
    assert all(np.array_equal(o["x"], good["x"] * 2.0) for o in out)
    assert proc.stats["batched_bursts"] == 0      # demoted: host chain now
    assert proc.stats["device_fallbacks"] == 0    # not a payload fallback
    assert proc.stats["device_demotions"] == 1    # … but a counted demotion


def test_device_demotion_is_logged_and_surfaced(monkeypatch, caplog):
    """A failing device program never turns into host execution unseen: the
    demotion is counted on the sidecar and logged as a warning."""
    if not fusion.jax_available():
        pytest.skip("device demotion needs jax")
    monkeypatch.setenv("DATAX_FUSION_JIT", "always")
    app = App("demotion-metrics")

    @app.driver(emits=TEN)
    def src(ctx, n=4):
        return ({"x": np.full((8, 8), float(i), np.float32)}
                for i in range(n))

    (app.sense("raw", src)
        .map(lambda p: {"x": p["x"] * (2.0 if float(p["x"].sum()) >= 0
                                       else 1.0)},
             emits=TEN, device=True, name="m1")
        .map(lambda p: {"x": p["x"] + 1}, emits=TEN, device=True,
             name="exit"))
    with caplog.at_level("WARNING", logger="repro.core.fusion"):
        with connect(start=False) as op:
            app.deploy(op, start_sensors=False)
            sub = op.subscribe("exit", maxsize=16)
            op.start_pending_sensors()
            out = [m.payload for m in drain(sub, 4, timeout=30)]
            metrics = op.executor.instances_of("exit")[0].sidecar.metrics()
    assert [float(o["x"][0, 0]) for o in out] == [1.0, 3.0, 5.0, 7.0]
    assert metrics["device_demotions"] == 1       # exposed on the sidecar
    assert metrics["device_fallbacks"] == 0       # not a payload fallback
    assert any("device program failed" in r.getMessage()
               for r in caplog.records)


def test_ragged_burst_degrades_per_message_and_stays_device(monkeypatch):
    if not fusion.jax_available():
        pytest.skip("ragged-burst degradation needs jax")
    monkeypatch.delenv("DATAX_FUSION_JIT", raising=False)
    monkeypatch.setattr(fusion, "JIT_MODE", "always")
    proc = _proc([_stage("map", lambda p: {"x": p["x"] * 2})], max_batch=4)
    ragged = [{"x": np.arange(n, dtype=np.float32)} for n in (2, 3, 2)]
    out = proc.process_batch("s", [dict(p) for p in ragged])
    for o, p in zip(out, ragged):
        assert np.array_equal(o["x"], p["x"] * 2)
    assert proc.stats["unstackable_bursts"] == 1  # the burst degraded …
    assert proc.stats["device_fallbacks"] == 0    # … but stayed on-device
    # stackable bursts afterwards still run batched
    uniform = [{"x": np.arange(3, dtype=np.float32)}] * 2
    proc.process_batch("s", [dict(p) for p in uniform])
    assert proc.stats["batched_bursts"] == 1


def test_device_fallbacks_surface_in_sidecar_metrics(monkeypatch):
    if not fusion.jax_available():
        pytest.skip("device fallback metrics need jax")
    monkeypatch.setenv("DATAX_FUSION_JIT", "always")
    app = App("fallback-metrics")

    @app.driver()  # untyped: lets a non-numeric payload through
    def src(ctx, n=4):
        def gen():
            for i in range(n):
                yield ({"x": "bad"} if i == 1
                       else {"x": np.full((4,), float(i), np.float32)})
        return gen()

    (app.sense("raw", src)
        .map(lambda p: {"x": p["x"] * 2}, device=True, name="m1")
        .map(lambda p: {"x": p["x"] * 1}, device=True, name="exit"))
    with connect(start=False) as op:
        app.deploy(op, start_sensors=False)
        sub = op.subscribe("exit", maxsize=16)
        op.start_pending_sensors()
        out = [m.payload for m in drain(sub, 4, timeout=30)]
        metrics = op.executor.instances_of("exit")[0].sidecar.metrics()
    assert out[1]["x"] == "badbad"                # host chain result
    assert np.array_equal(out[2]["x"], np.full((4,), 4.0, np.float32))
    assert metrics["device_fallbacks"] == 1       # exposed on the sidecar


# ---------------------------------------------------------------------------
# (e) bugfix: poison messages are accounted and backlog re-homed
# ---------------------------------------------------------------------------

def _poison_executor():
    bus_ = MessageBus()
    bus_.register_subject("in", INT_SCHEMA)
    bus_.register_subject("out", INT_SCHEMA)
    ex = Executor(bus_)

    def logic(ctx):
        def process(stream, payload):
            if payload["value"] < 0:
                raise RuntimeError("poison")
            return {"value": payload["value"]}
        return process

    return bus_, ex, logic


def test_poison_message_lands_on_subject_lost_stat():
    bus_, ex, logic = _poison_executor()
    try:
        h = ex.start_instance(entity_kind="analytics_unit", entity_name="au",
                              owner="w", logic=logic, config={},
                              inputs=("in",), output="out", group="w")
        tok = bus_.issue_token("pub", ["in"])
        bus_.publish("in", {"value": -1}, token=tok)
        h.thread.join(timeout=10)
        assert h.crashed
        # the popped copy was the only one — it must not vanish uncounted
        assert bus_.stats()["in"]["lost"] == 1
    finally:
        ex.shutdown()
        bus_.close()


def test_poison_burst_counts_every_inflight_message():
    bus_ = MessageBus()
    bus_.register_subject("in", INT_SCHEMA)
    sc = Sidecar("i", bus_, inputs=("in",))
    tok = bus_.issue_token("pub", ["in"])
    for i in range(4):
        bus_.publish("in", {"value": i}, token=tok)

    def process(stream, payload):
        raise RuntimeError("poison")

    def process_batch(stream, payloads):
        raise RuntimeError("poison burst")
    process.process_batch = process_batch
    process.default_max_batch = 8
    with pytest.raises(RuntimeError):
        Executor._pump(process, sc, threading.Event(), sink=False)
    assert bus_.stats()["in"]["lost"] == 4
    sc.close()
    bus_.close()


def test_poison_mid_burst_emits_prefix_and_counts_only_tail(monkeypatch):
    """A poison message partway through a burst must not destroy its
    already-processed predecessors: the fused unit's per-message fallback
    hands the successful prefix back (BatchInterrupted), the pump emits it,
    and only the poison + unprocessed tail count as lost."""
    monkeypatch.setattr(fusion, "_HAS_JAX", False)   # host-chain burst mode

    def boom_factory(ctx):
        def proc(stream, payload):
            if payload["value"] < 0:
                raise RuntimeError("poison")
            return {"value": payload["value"] * 2}
        return proc

    stages = [FusedStage(au_name="au", stream_name="st",
                         factory=boom_factory, config={}, kind="au",
                         pure_fn=None)]
    proc = make_fused_logic(stages, None, max_batch=8)(LogicContext({}))
    bus_ = MessageBus()
    bus_.register_subject("in", INT_SCHEMA)
    bus_.register_subject("out", INT_SCHEMA)
    sc = Sidecar("i", bus_, inputs=("in",), output="out")
    out = bus_.subscribe("out", token=bus_.issue_token("ext", ["out"]))
    tok = bus_.issue_token("pub", ["in"])
    for v in (1, 2, -1, 4, 5):
        bus_.publish("in", {"value": v}, token=tok)
    from repro.core import BatchInterrupted
    with pytest.raises(BatchInterrupted):
        Executor._pump(proc, sc, threading.Event(), sink=False)
    # prefix flowed downstream before the crash …
    assert [m.payload["value"] for m in drain(out, 2, timeout=5)] == [2, 4]
    # … and only the poison and the unprocessed tail are lost
    assert bus_.stats()["in"]["lost"] == 3
    sc.close()
    bus_.close()


def test_reap_rehomes_crashed_members_backlog_to_survivors():
    """Regression: reap -> depart() hands the crashed member's remaining
    mailbox backlog to the group survivors; only the poison message is lost,
    and it is counted."""
    bus_, ex, logic = _poison_executor()
    try:
        a = ex.start_instance(entity_kind="analytics_unit", entity_name="au",
                              owner="w", logic=logic, config={},
                              inputs=("in",), output="out", group="w")
        ex.start_instance(entity_kind="analytics_unit", entity_name="au",
                          owner="w", logic=logic, config={},
                          inputs=("in",), output="out", group="w")
        out = bus_.subscribe("out", token=bus_.issue_token("ext", ["out"]),
                             maxsize=64)
        tok = bus_.issue_token("pub", ["in"])
        # round-robin cursor starts at the first member: the poison goes to a
        bus_.publish("in", {"value": -1}, token=tok)
        a.thread.join(timeout=10)
        assert a.crashed
        # a is dead but not yet reaped: round-robin still deals it a share,
        # which queues in its mailbox with nobody left to drain it
        for i in range(20):
            bus_.publish("in", {"value": i}, token=tok)
        dead = ex.reap_dead()
        assert [h.instance_id for h in dead] == [a.instance_id]
        vals = sorted(m.payload["value"] for m in drain(out, 20, timeout=10))
        assert vals == list(range(20))           # nothing lost but the poison
        st = bus_.stats()["in"]
        assert st["lost"] == 1                   # the poison, counted
        assert st["groups"]["w"]["rerouted"] > 0  # backlog re-homed, not lost
    finally:
        ex.shutdown()
        bus_.close()


def test_reconciler_restarts_poisoned_instance_and_stream_recovers():
    """End to end: poison crashes the only instance, the loss is counted,
    the reconciler restarts it, and the stream keeps flowing."""
    op = Operator(reconcile_interval_s=0.05)
    try:
        op.register_driver(DriverSpec(
            name="quiet", logic=lambda ctx: iter(()),
            output_schema=INT_SCHEMA))
        op.register_analytics_unit(AnalyticsUnitSpec(
            name="fragile",
            logic=lambda ctx: (lambda s, p:
                               (_ for _ in ()).throw(RuntimeError("poison"))
                               if p["value"] < 0 else {"value": p["value"]}),
            output_schema=INT_SCHEMA))
        op.register_sensor(SensorSpec(name="nums", driver="quiet"),
                           start=False)
        op.create_stream(StreamSpec(name="outs", analytics_unit="fragile",
                                    inputs=("nums",), fixed_instances=1))
        op.start()
        sub = op.subscribe("outs")
        tok = op.bus.issue_token("pub", ["nums"])
        op.bus.publish("nums", {"value": -1}, token=tok)     # poison
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if any(k == "restart" for _, k, _d in op.events):
                break
            time.sleep(0.02)
        assert any(k == "restart" for _, k, _d in op.events)
        op.bus.publish("nums", {"value": 7}, token=tok)      # flows again
        assert drain(sub, 1, timeout=10)[0].payload["value"] == 7
        assert op.bus.stats()["nums"]["lost"] == 1
    finally:
        op.shutdown()


# ---------------------------------------------------------------------------
# plumbing: DSL .scaled(max_batch=) -> StreamSpec -> fused unit
# ---------------------------------------------------------------------------

def _device_chain_app(max_batch=None, on="exit", mid_batch=None):
    app = App("knob")

    @app.driver(emits=TEN)
    def src(ctx):
        return iter(())

    h1 = app.sense("raw", src).map(lambda p: p, emits=TEN, device=True,
                                   name="mid")
    h2 = h1.map(lambda p: p, emits=TEN, device=True, name="exit")
    if mid_batch is not None:
        h1.scaled(max_batch=mid_batch)
    if max_batch is not None:
        (h1 if on == "mid" else h2).scaled(max_batch=max_batch)
    return app


def test_scaled_max_batch_reaches_fused_stream_spec():
    built = _device_chain_app(max_batch=16).build()
    assert built.streams[0].max_batch == 16
    # declared on an INTERIOR stage: fusion folds it onto the fused unit
    built = _device_chain_app(max_batch=4, on="mid").build()
    assert built.streams[0].max_batch == 4
    # no knob -> platform default applies at the unit, spec stays None
    assert _device_chain_app().build().streams[0].max_batch is None
    # conflicting declarations: the stage closest to the exit wins, so a
    # trailing max_batch=1 really does force per-message dispatch
    built = _device_chain_app(max_batch=1, mid_batch=32).build()
    assert built.streams[0].max_batch == 1


def test_scaled_max_batch_validation():
    with pytest.raises(DSLError):
        _device_chain_app(max_batch=0)


def test_operator_rejects_bad_max_batch():
    op = Operator()
    try:
        op.register_driver(DriverSpec(
            name="counter", logic=lambda ctx: iter(()),
            config_schema=ConfigSchema.empty(), output_schema=INT_SCHEMA))
        op.register_analytics_unit(AnalyticsUnitSpec(
            name="ident", logic=lambda ctx: (lambda s, p: p),
            output_schema=INT_SCHEMA))
        op.register_sensor(SensorSpec(name="nums", driver="counter"),
                           start=False)
        with pytest.raises(OperatorError):
            op.create_stream(StreamSpec(name="out", analytics_unit="ident",
                                        inputs=("nums",), max_batch=0))
    finally:
        op.shutdown()
