"""Host spans and their counters: the fused unit driven by the executor's
real pump, the serving engine's tick, and the spans' place in a profiler
trace beside the device's operations."""
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import (Executor, MessageBus, Sidecar,  # noqa: E402
                        StreamSchema, drain)
from repro.core import fusion  # noqa: E402
from repro.core.fusion import FusedStage, make_fused_logic  # noqa: E402
from repro.core.sdk import LogicContext  # noqa: E402
from repro.core.spans import span  # noqa: E402

SCHEMA = StreamSchema.device(x=((4, 16), "float32"))
PUMP_SPANS = ("pull_s", "emit_s")
FUSED_SPANS = ("stack_s", "upload_s", "program_s", "download_s", "unstack_s")


@pytest.fixture
def jit_always(monkeypatch):
    monkeypatch.setenv("DATAX_FUSION_JIT", "always")


def _fused_process(max_batch):
    stages = []
    for i, (kind, fn) in enumerate([
            ("map", lambda p: {"x": p["x"] * 2.0}),
            ("filter", lambda p: p["x"].max() > 0.0),
            ("map", lambda p: {"x": jnp.tanh(p["x"])})]):
        def factory(ctx, fn=fn):
            return lambda stream, payload: fn(payload)
        stages.append(FusedStage(au_name=f"au{i}", stream_name=f"s{i}",
                                 factory=factory, config={}, kind=kind,
                                 pure_fn=fn))
    ctx = LogicContext({}, db=None, instance_id="test")
    return make_fused_logic(stages, SCHEMA, max_batch=max_batch)(ctx)


def _frames(n):
    rng = np.random.default_rng(0)
    # every frame has a positive element, so the filter keeps it
    return [{"x": np.abs(rng.standard_normal((4, 16))).astype(np.float32)}
            for _ in range(n)]


def _pump(n, max_batch, on_start=None):
    """Publish ``n`` frames, then pump them through a fused unit until all
    come back; returns (sidecar metrics, the pump thread's wall seconds)."""
    bus = MessageBus()
    bus.register_subject("in", SCHEMA)
    bus.register_subject("out", SCHEMA)
    out = bus.subscribe("out", token=bus.issue_token("client", ["out"]),
                        maxsize=4 * n)
    sidecar = Sidecar("unit", bus, inputs=["in"], output="out",
                      queue_size=4 * n)
    proc = _fused_process(max_batch)
    proc.warmup()
    tok = bus.issue_token("camera", ["in"])
    for p in _frames(n):
        bus.publish("in", p, token=tok)
    if on_start is not None:
        on_start()
    stop = threading.Event()
    pump = threading.Thread(target=Executor._pump,
                            args=(proc, sidecar, stop, False, max_batch))
    t0 = time.perf_counter()
    pump.start()
    try:
        assert len(drain(out, n, timeout=60)) == n
    finally:
        stop.set()
        pump.join(timeout=10)
    wall = time.perf_counter() - t0
    assert not pump.is_alive()
    return sidecar.metrics(), wall


@pytest.mark.parametrize("mesh", [False, True], ids=["single", "mesh1"])
def test_pump_counters_cover_every_burst(jit_always, monkeypatch, mesh):
    if mesh:
        from jax.sharding import Mesh
        one = Mesh(np.array(jax.devices()[:1]), ("data",))
        monkeypatch.setattr(fusion, "fusion_mesh", lambda: one)
    m, wall = _pump(64, max_batch=16)
    assert m["mailbox_msgs"] == m["batch_msgs"] == 64
    assert m["batched_bursts"] == 4
    # the warm-up allocates the kept host buffer, and every burst reuses it
    assert (m["stage_allocs"], m["stage_reuses"]) == (1, 4)
    assert m["mailbox_wait_s"] > 0
    for key in PUMP_SPANS + FUSED_SPANS:
        assert m[key] > 0, key
    # the counted spans do not nest in one another, so together they fit
    # in the pump's wall time
    assert sum(m[k] for k in PUMP_SPANS + FUSED_SPANS) <= wall
    if mesh:
        assert m["sharded_bursts"] == 4
        assert 0 < m["place_s"] <= m["program_s"]   # placement is inside
    else:
        assert m["place_s"] == 0.0


def test_span_adds_its_duration_to_its_counter():
    counters = {"t_s": 0.0}
    t0 = time.perf_counter()
    with span("datax.test.block", counters, "t_s", seq=7):
        time.sleep(0.01)
    assert 0.01 <= counters["t_s"] <= time.perf_counter() - t0
    with span("datax.test.bare"):     # no counter: annotation only
        pass


def test_engine_counters_split_a_tick():
    from repro import models
    from repro.configs import get_smoke_config
    from repro.configs.base import RunConfig
    from repro.serve import ServeEngine

    cfg = get_smoke_config("qwen3-32b")
    params = models.init(jax.random.PRNGKey(0), cfg)
    run = RunConfig(attention_impl="naive", remat="none",
                    attention_chunk=16)

    def serve(n_requests):
        eng = ServeEngine(cfg, run, params, n_slots=2, max_seq=64)
        for i in range(n_requests):
            eng.submit(f"r{i}", [1 + i, 2, 3, 4], max_new_tokens=4)
        done = {r.request_id: r for r in eng.run_until_idle()}
        assert len(done) == n_requests
        return eng.metrics, done

    fits, _ = serve(2)
    crowded, done = serve(4)
    for m in (fits, crowded):
        assert m["tick_s"] >= m["device_wait_s"] > 0
        assert m["prefills"] > 0 and m["slot_wait_s"] >= 0
    assert crowded["slot_wait_s"] == pytest.approx(
        sum(r.admitted_at - r.arrived for r in done.values()))
    # r2 and r3 each waited for r0 or r1 to finish: at least r0's service
    service = done["r0"].finished_at - done["r0"].admitted_at
    assert crowded["slot_wait_s"] >= 2 * service
    assert crowded["slot_wait_s"] > fits["slot_wait_s"]


def test_spans_nest_on_the_profilers_host_plane(jit_always, tmp_path):
    from jax.profiler import ProfileData

    from repro.core.spans import TraceAnnotation
    if TraceAnnotation is None:
        pytest.skip("no profiler annotation without jax.profiler")
    started = []

    def start():
        jax.profiler.start_trace(str(tmp_path))
        started.append(True)
    try:
        _pump(8, max_batch=8, on_start=start)
    finally:
        if started:
            jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    events: dict = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                events.setdefault(ev.name, []).append(
                    (s, s + int(ev.duration_ns)))
    bursts = events["datax.pump.burst"]
    assert len(bursts) == 1
    lo, hi = bursts[0]
    # (the pump's warm-up uploads too, outside any burst)
    for name in ("datax.pump.emit", "datax.fused.upload",
                 "datax.fused.program_wait"):
        assert any(lo <= s and e <= hi for s, e in events[name]), name
