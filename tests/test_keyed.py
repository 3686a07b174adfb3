"""Keyed delivery (tentpole PR 4): hash-partitioned streams + per-key state.

Bus level: ``subscribe(..., group=..., key=...)`` pins every key to one
healthy member via a stable partition ring (rendezvous hashing); a departing
member's partitions — and its queued backlog — re-home to survivors whole
and in order.

Platform level: ``StreamSpec(delivery="keyed", key=...)`` plumbs the policy
through operator/executor/sidecar; the DSL grows ``.key_by`` and per-key
stateful combinators (``.reduce``, ``.window(per_key=True)``) whose state
lives in the stream's shared platform database (``KeyedStore``), so
``.scaled()`` pools survive partition rebalances without losing state; the
autoscaler reads per-partition backlog; fused units inherit the entry
stream's key policy and barrier on mid-chain keyed consumers.
"""
import threading
import time

import pytest

from repro.core import (AnalyticsUnitSpec, App, AutoScaler, CoherenceError,
                        ConfigSchema, DriverSpec, DSLError, FieldSpec,
                        KeyedStore, MessageBus, Operator, OperatorError,
                        ScalePolicy, SensorSpec, StreamSchema, StreamSpec,
                        connect, drain, partition_of, ring_assignment)
from repro.core.bus import KEYED_PARTITIONS, BusError

KV = StreamSchema.of(k=FieldSpec("str"), v=FieldSpec("int"))


def _drain_now(sub):
    out = []
    while True:
        m = sub.next(timeout=0)
        if m is None:
            return out
        out.append(m.payload)


# ---------------------------------------------------------------------------
# Bus-level semantics
# ---------------------------------------------------------------------------

@pytest.fixture
def bus():
    b = MessageBus()
    b.register_subject("s", KV)
    return b


def test_same_key_same_member_in_order(bus):
    tok = bus.issue_token("t", ["s"])
    members = [bus.subscribe("s", token=tok, group="pool", key="k",
                             name=f"m{i}") for i in range(3)]
    keys = [f"key-{i}" for i in range(12)]
    for v in range(5):
        for k in keys:
            bus.publish("s", {"k": k, "v": v}, token=tok)
    owner: dict[str, str] = {}
    seen: dict[str, list[int]] = {}
    for m in members:
        for p in _drain_now(m):
            assert owner.setdefault(p["k"], m.name) == m.name, \
                f"key {p['k']} split across members"
            seen.setdefault(p["k"], []).append(p["v"])
    assert sorted(seen) == sorted(keys)          # every key delivered
    assert all(vals == [0, 1, 2, 3, 4] for vals in seen.values())


def test_keyed_group_stats_surface_ring(bus):
    tok = bus.issue_token("t", ["s"])
    bus.subscribe("s", token=tok, group="pool", key="k", name="a")
    bus.subscribe("s", token=tok, group="pool", key="k", name="b")
    for i in range(6):
        bus.publish("s", {"k": f"x{i}", "v": i}, token=tok)
    g = bus.stats()["s"]["groups"]["pool"]
    assert g["policy"] == "keyed" and g["key"] == "k"
    assert g["delivered"] == 6
    assert len(g["assignment"]) == KEYED_PARTITIONS
    assert set(g["assignment"].values()) <= {"a", "b"}
    # exact per-partition backlog: 6 queued messages across partitions
    assert sum(g["partition_backlog"].values()) == 6
    # ...and it drains to zero as members consume
    for sub in list(bus._subs["s"]):
        _drain_now(sub)
    assert bus.stats()["s"]["groups"]["pool"]["partition_backlog"] == {}


def test_departing_member_partitions_rehome_in_order(bus):
    """Scale-down: the leaver's queued backlog re-homes per partition (to
    the rendezvous runner-up), ordered BEFORE any newer message for those
    keys; surviving members' keys are untouched."""
    tok = bus.issue_token("t", ["s"])
    a = bus.subscribe("s", token=tok, group="pool", key="k", name="a")
    b = bus.subscribe("s", token=tok, group="pool", key="k", name="b")
    keys = [f"key-{i}" for i in range(10)]
    for v in range(3):
        for k in keys:
            bus.publish("s", {"k": k, "v": v}, token=tok)
    assert a.qsize() and b.qsize()          # both members own some keys
    bus.unsubscribe(a)
    for v in range(3, 5):
        for k in keys:
            bus.publish("s", {"k": k, "v": v}, token=tok)
    seen: dict[str, list[int]] = {}
    for p in _drain_now(b):
        seen.setdefault(p["k"], []).append(p["v"])
    assert sorted(seen) == sorted(keys)
    for k, vals in seen.items():
        assert vals == [0, 1, 2, 3, 4], (k, vals)   # in order, none lost
    assert bus.stats()["s"]["groups"]["pool"]["rerouted"] > 0


def test_keyed_wire_members_roundtrip(bus):
    tok = bus.issue_token("t", ["s"])
    w = bus.subscribe("s", token=tok, group="pool", key="k", name="w",
                      wire=True)
    bus.publish("s", {"k": "x", "v": 1}, token=tok)
    msg = w.next(timeout=1)
    assert msg.payload == {"k": "x", "v": 1}


def test_keyed_policy_mismatch_rejected(bus):
    tok = bus.issue_token("t", ["s"])
    bus.subscribe("s", token=tok, group="pool", key="k", name="a")
    with pytest.raises(BusError):
        bus.subscribe("s", token=tok, group="pool", name="b")      # no key
    with pytest.raises(BusError):
        bus.subscribe("s", token=tok, group="pool", key="v", name="c")
    with pytest.raises(BusError):
        bus.subscribe("s", token=tok, group="pool", key="k", name="d",
                      partitions=16)         # ring size fixed at creation
    with pytest.raises(BusError):
        # duplicate member name would collapse both onto one ring identity
        bus.subscribe("s", token=tok, group="pool", key="k", name="a")
    with pytest.raises(BusError):
        bus.subscribe("s", token=tok, group="p2", key="k", partitions=0)
    bus2 = MessageBus()
    bus2.register_subject("s", KV)
    tok2 = bus2.issue_token("t", ["s"])
    bus2.subscribe("s", token=tok2, group="g", name="plain")
    with pytest.raises(BusError):
        bus2.subscribe("s", token=tok2, group="g", key="k", name="keyed")
    with pytest.raises(BusError):
        bus2.subscribe("s", token=tok2, key="k", name="keyed-ungrouped")


def test_missing_key_field_routes_deterministically(bus):
    """Payloads without the key field all hash the same (key None) — they
    stay single-member and ordered rather than being scattered."""
    bus_ = MessageBus()
    bus_.register_subject("u")            # untyped subject
    tok = bus_.issue_token("t", ["u"])
    members = [bus_.subscribe("u", token=tok, group="pool", key="k",
                              name=f"m{i}") for i in range(3)]
    for i in range(6):
        bus_.publish("u", {"v": i}, token=tok)
    got = [len(_drain_now(m)) for m in members]
    assert sorted(got) == [0, 0, 6]


# ---------------------------------------------------------------------------
# The partition ring: stability + minimal disruption (hypothesis)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except Exception:  # pragma: no cover - minimal-deps CI leg
    _HAS_HYPOTHESIS = False

if _HAS_HYPOTHESIS:
    _members = st.lists(st.text("abcdefgh0123-", min_size=1, max_size=12),
                        unique=True, min_size=1, max_size=8)

    @settings(max_examples=60, deadline=None)
    @given(_members, st.sampled_from([8, 32, 64]), st.data())
    def test_ring_stable_and_minimally_disruptive(members, nparts, data):
        """Same membership -> identical assignment (same key, same member);
        a single leave moves exactly the leaver's partitions (each to its
        runner-up); a single join moves exactly the partitions the joiner
        wins.  No unrelated partition ever moves."""
        before = ring_assignment(members, nparts)
        assert before == ring_assignment(list(members), nparts)  # stable

        leaver = data.draw(st.sampled_from(members), label="leaver")
        survivors = [m for m in members if m != leaver]
        if survivors:
            after = ring_assignment(survivors, nparts)
            moved = {p for p in range(nparts) if after[p] != before[p]}
            owned = {p for p, o in before.items() if o == leaver}
            assert moved == owned                 # == |leaver's partitions|

        joiner = data.draw(st.text("xyz987", min_size=1, max_size=12)
                           .filter(lambda s: s not in members),
                           label="joiner")
        grown = ring_assignment(members + [joiner], nparts)
        moved = {p for p in range(nparts) if grown[p] != before[p]}
        assert all(grown[p] == joiner for p in moved)
        assert moved == {p for p, o in grown.items() if o == joiner}

    @settings(max_examples=40, deadline=None)
    @given(st.one_of(st.text(max_size=20), st.integers(), st.binary(max_size=16),
                     st.none()),
           st.sampled_from([8, 64]))
    def test_partition_of_is_stable_and_in_range(key, nparts):
        p = partition_of(key, nparts)
        assert 0 <= p < nparts
        assert p == partition_of(key, nparts)


# ---------------------------------------------------------------------------
# Operator level
# ---------------------------------------------------------------------------

def kv_driver(ctx):
    def gen():
        for v in range(int(ctx.config.get("rounds", 5))):
            for i in range(int(ctx.config.get("keys", 6))):
                if not ctx.running:
                    return
                yield {"k": f"key-{i}", "v": v}
    return gen()


def counting_au(ctx):
    """Per-key counter whose state lives in the platform database."""
    store = KeyedStore(ctx.db, "counts")

    def process(stream, payload):
        n = store.get(payload["k"], 0) + 1
        store.put(payload["k"], n)
        return {"k": payload["k"], "v": n}
    return process


def _operator() -> Operator:
    op = Operator(reconcile_interval_s=0.05)
    op.register_driver(DriverSpec(
        name="kv", logic=kv_driver,
        config_schema=ConfigSchema.of(rounds=("int", 5), keys=("int", 6)),
        output_schema=KV))
    return op


def test_keyed_stream_spec_validation():
    op = _operator()
    try:
        op.register_analytics_unit(AnalyticsUnitSpec(
            name="count", logic=counting_au, output_schema=KV,
            stateful=True))
        op.register_sensor(SensorSpec(name="events", driver="kv"))
        with pytest.raises(OperatorError):
            op.create_stream(StreamSpec(name="c1", analytics_unit="count",
                                        inputs=("events",), delivery="keyed"))
        with pytest.raises(OperatorError):
            op.create_stream(StreamSpec(name="c2", analytics_unit="count",
                                        inputs=("events",), key="k"))
        with pytest.raises(CoherenceError):
            op.create_stream(StreamSpec(name="c3", analytics_unit="count",
                                        inputs=("events",), delivery="keyed",
                                        key="nope"))
        op.create_stream(StreamSpec(name="c4", analytics_unit="count",
                                    inputs=("events",), delivery="keyed",
                                    key="k"))
    finally:
        op.shutdown()


def test_keyed_stateful_pool_scale_down_keeps_state():
    """4 keyed instances count per key; stopping one mid-run re-homes its
    partitions to survivors that read the same store — every key's final
    count is exact and every emission is in per-key order."""
    rounds, keys = 8, 8
    op = _operator()
    try:
        op.register_analytics_unit(AnalyticsUnitSpec(
            name="count", logic=counting_au, output_schema=KV,
            stateful=True, max_instances=8))
        op.register_sensor(SensorSpec(name="events", driver="kv",
                                      config={"rounds": rounds,
                                              "keys": keys}), start=False)
        op.create_stream(StreamSpec(name="counts", analytics_unit="count",
                                    inputs=("events",), fixed_instances=4,
                                    delivery="keyed", key="k"))
        handles = op.executor.instances_of("counts")
        assert len(handles) == 4
        assert all(h.sidecar.key == "k" for h in handles)
        sub = op.subscribe("counts")
        op.start_pending_sensors()
        time.sleep(0.05)
        op.executor.stop_instance(handles[0].instance_id)   # forced leave
        msgs = drain(sub, rounds * keys, timeout=20)
        per_key: dict[str, list[int]] = {}
        for m in msgs:
            per_key.setdefault(m.payload["k"], []).append(m.payload["v"])
        for k, vals in per_key.items():
            assert vals == list(range(1, rounds + 1)), (k, vals)
        table = op.store.get("au-counts").table("counts")
        for i in range(keys):
            assert table.get(f"key-{i}")["value"] == rounds
    finally:
        op.shutdown()


def test_keyed_stateful_pool_keeps_state_under_stealing():
    """A keyed fold pool with one straggler member and stealing on: idle
    members take the straggler's queued partitions, and every key's folds
    still arrive once each, in order, with the accumulator never reset."""
    rounds, keys = 4, 64
    app = App("keyed-steal")

    @app.driver(emits=KV)
    def src(ctx):
        return ({"k": f"key-{i}", "v": v}
                for v in range(rounds) for i in range(keys))

    straggler: dict = {}
    claim = threading.Lock()

    def fold(acc, payload):
        # the first member to fold becomes the straggler, 20x slower
        me = threading.get_ident()
        with claim:
            slow = straggler.setdefault("ident", me) == me
        time.sleep(0.02 if slow else 0.001)
        return {"n": (acc or {"n": 0})["n"] + 1, "v": payload["v"]}

    (app.sense("events", src).key_by("k")
        .reduce(fold, name="folds").scaled(instances=4, steal=True))
    with connect(start=False) as op:
        app.deploy(op, start_sensors=False)
        sub = op.subscribe("folds", maxsize=rounds * keys + 8)
        op.start_pending_sensors()
        msgs = drain(sub, rounds * keys, timeout=30)
        stolen = op.bus.stats()["events"]["groups"]["folds"]["stolen"]
    per_key: dict[str, list[dict]] = {}
    for m in msgs:
        per_key.setdefault(m.payload["k"], []).append(m.payload["value"])
    assert len(per_key) == keys
    for k, folds in per_key.items():
        assert [f["v"] for f in folds] == list(range(rounds)), k
        assert [f["n"] for f in folds] == list(range(1, rounds + 1)), k
    assert stolen > 0                    # the steal path actually ran


def test_sidecar_metrics_surface_lag_and_assignment():
    op = _operator()
    try:
        op.register_analytics_unit(AnalyticsUnitSpec(
            name="count", logic=counting_au, output_schema=KV,
            stateful=True))
        op.register_sensor(SensorSpec(name="events", driver="kv"), start=False)
        op.create_stream(StreamSpec(name="counts", analytics_unit="count",
                                    inputs=("events",), fixed_instances=2,
                                    delivery="keyed", key="k"))
        h = op.executor.instances_of("counts")[0]
        m = h.sidecar.metrics()
        assert m["key"] == "k"
        info = m["groups"]["events"]
        assert info["policy"] == "keyed" and info["key"] == "k"
        assert info["members"] == 2
        assert set(info["assignment"].values()) <= \
            {x.sidecar._subs[0].name for x in
             op.executor.instances_of("counts")}
        assert "lag" in info and "partition_backlog" in info
    finally:
        op.shutdown()


# ---------------------------------------------------------------------------
# DSL level
# ---------------------------------------------------------------------------

def _kv_app():
    app = App("keyed-dsl")

    @app.driver(emits=KV)
    def src(ctx, rounds=5, keys=6):
        return ({"k": f"key-{i}", "v": v}
                for v in range(rounds) for i in range(keys))
    return app, app.sense("events", src)


def test_key_by_validates_field():
    _, events = _kv_app()
    with pytest.raises(DSLError):
        events.key_by("nope")
    assert events.key_by("k").key == "k"
    assert events.key is None            # handles are immutable descriptors


def test_reduce_requires_key_by():
    _, events = _kv_app()
    with pytest.raises(DSLError):
        events.reduce(lambda acc, p: acc)
    with pytest.raises(DSLError):
        events.window(3, per_key=True)


def test_keyed_combinators_compile_to_keyed_specs():
    app, events = _kv_app()
    counts = events.key_by("k").reduce(lambda a, p: (a or 0) + 1,
                                       name="counts").scaled(instances=3)
    spec = next(s for s in app._streams if s.name == "counts")
    assert spec.delivery == "keyed" and spec.key == "k"
    assert spec.fixed_instances == 3
    assert app._aus[spec.analytics_unit].stateful
    assert counts.key == "k"             # reduce emits the key field


def test_scaled_guards_on_keyed_streams():
    app, events = _kv_app()
    win = events.key_by("k").window(3, per_key=True, name="w")
    win.scaled(instances=2)              # keyed stateful stage CAN scale now
    spec = next(s for s in app._streams if s.name == "w")
    assert spec.fixed_instances == 2 and spec.delivery == "keyed"
    with pytest.raises(DSLError):
        win.scaled(delivery="broadcast")     # would discard the key policy
    with pytest.raises(DSLError):
        win.scaled(delivery="group")
    # unkeyed stateful combinators remain pinned
    unkeyed = events.window(3, name="w2")
    with pytest.raises(DSLError):
        unkeyed.scaled(instances=2)


def test_keyed_map_propagates_and_typed_schema_breaks_chain():
    app, events = _kv_app()
    keyed = events.key_by("k")
    kept = keyed.map(lambda p: p, name="m1")             # untyped out
    assert kept.key == "k"
    NO_K = StreamSchema.of(v=FieldSpec("int"))
    dropped = keyed.map(lambda p: {"v": p["v"]}, emits=NO_K, name="m2")
    assert dropped.key is None
    spec = next(s for s in app._streams if s.name == "m2")
    assert spec.delivery == "keyed"      # the stage itself still keyed


def test_keyed_window_per_key_flow():
    app, events = _kv_app()
    (events.key_by("k").window(2, per_key=True, name="pairs")
        .scaled(instances=2))
    with connect(start=False) as op:
        app.deploy(op, start_sensors=False)
        sub = op.subscribe("pairs", maxsize=64)
        op.start_pending_sensors()
        msgs = drain(sub, 12, timeout=10)    # 6 keys x 5 rounds -> 2 windows
        for m in msgs:
            w = m.payload["window"]
            assert len(w) == 2 and len({x["k"] for x in w}) == 1
            assert [x["v"] for x in w] in ([0, 1], [2, 3])
        assert sub.next(timeout=0.2) is None  # round 4 stays buffered


def test_keyed_fused_entry_inherits_key_policy():
    app = App("keyed-fused")

    @app.driver(emits=KV)
    def src(ctx, n=5):
        return ({"k": f"key-{i}", "v": i} for i in range(n))

    (app.sense("raw", src)
        .key_by("k")
        .map(lambda p: {"k": p["k"], "v": p["v"] + 1}, emits=KV,
             device=True, name="a")
        .map(lambda p: {"k": p["k"], "v": p["v"] * 2}, emits=KV,
             device=True, name="b"))
    built = app.build()
    fused = [s for s in built.streams if s.name == "b"]
    assert len(fused) == 1
    assert fused[0].delivery == "keyed" and fused[0].key == "k"
    assert any(a.fused_stages for a in built.analytics_units)


def test_mid_chain_keyed_consumer_is_fusion_barrier():
    app = App("keyed-barrier")

    @app.driver(emits=KV)
    def src(ctx, n=5):
        return ({"k": f"key-{i}", "v": i} for i in range(n))

    stage_a = app.sense("raw", src).map(
        lambda p: {"k": p["k"], "v": p["v"] + 1}, emits=KV, device=True,
        name="a")
    # re-partition point: the keyed consumer's input must stay on the bus
    stage_a.key_by("k").map(lambda p: {"k": p["k"], "v": p["v"] * 2},
                            emits=KV, device=True, name="b")
    built = app.build()
    assert not any(a.fused_stages for a in built.analytics_units)
    spec_b = next(s for s in built.streams if s.name == "b")
    assert spec_b.delivery == "keyed" and spec_b.inputs == ("a",)


# ---------------------------------------------------------------------------
# Autoscaler: per-partition backlog is a scale-up signal
# ---------------------------------------------------------------------------

class _FakeKeyedSidecar:
    def __init__(self, backlog, partition_backlog, key="k"):
        self._m = {"instance": f"fake-{id(self):x}", "backlog": backlog,
                   "idle_s": 0.0, "dropped": 0, "key": key,
                   "groups": {"in": {"policy": "keyed",
                                     "partition_backlog": partition_backlog}}}

    def metrics(self):
        return dict(self._m, received=0, published=0, processed=0,
                    errors=0, latency_ewma_s=0, uptime_s=1)


class _H:
    def __init__(self, backlog, partition_backlog, key="k"):
        self.sidecar = _FakeKeyedSidecar(backlog, partition_backlog, key)


def test_autoscaler_scales_up_on_hot_partition():
    scaler = AutoScaler(ScalePolicy(backlog_high=10, backlog_low=1,
                                    idle_s=0.0, cooldown_s=0.0))
    # aggregate is comfortable (12 < 2x10) but one partition holds 11
    # queued messages: a hot key pinned to one member -> scale up
    hot = _H(11, {3: 11})
    cold = _H(1, {})
    assert scaler.decide("s", [hot, cold], 1, 8) == 4
    # same shape unkeyed (no key field): aggregate rule only -> steady
    plain_hot = _H(11, {3: 11}, key=None)
    assert scaler.decide("t", [plain_hot, _H(1, {}, key=None)], 1, 8) == 2


def test_keyed_store_shared_across_instances(tmp_path):
    from repro.core import Database
    db = Database("shared")
    a = KeyedStore(db, "counts")
    b = KeyedStore(db, "counts")        # second instance, same platform db
    a.put("k1", 41)
    assert b.get("k1") == 41            # rebalanced partition finds state
    b.put("k1", b.get("k1") + 1)
    assert a.get("k1") == 42
    assert len(a) == 1 and a.keys() == ["k1"]
    a.delete("k1")
    assert b.get("k1", 0) == 0
    solo = KeyedStore(None, "local")    # db-less fallback for bare factories
    solo.put("x", 1)
    assert solo.get("x") == 1
