"""Serverless execution + autoscaling (paper §3 "Serverless stream processing").

The Executor is the platform's compute fabric: it runs every driver / AU /
actuator instance on worker threads, wrapped in a Sidecar.  Developers never
touch it — the Operator asks for instances and the Executor provides them,
which is the paper's serverless claim ("developers only provide the business
logic and actual execution is handled transparently").

The AutoScaler turns sidecar metrics into scale decisions — the paper: "these
metrics also drive the auto-scaling process".
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
import traceback
from typing import Any, Callable, Mapping, Sequence

from .bus import BusLike, MessageBus
from .delivery import DeliveryPolicy
from .sdk import BatchInterrupted, DataX, LogicContext, is_sdk_style
from .sidecar import Sidecar
from .spans import span
from .state import Database


@dataclasses.dataclass
class InstanceHandle:
    """One running instance of a driver/AU/actuator."""

    instance_id: str
    entity_kind: str                 # driver | analytics_unit | actuator
    entity_name: str                 # code-entity name
    owner: str                       # sensor/stream/gadget that requested it
    config: dict
    sidecar: Sidecar
    thread: threading.Thread
    stop_event: threading.Event
    node: str | None = None          # simulated placement (paper's USB affinity)
    started_at: float = dataclasses.field(default_factory=time.monotonic)
    crashed: bool = False
    completed: bool = False          # ran to normal end (e.g. finite driver)
    crash_info: str = ""

    def alive(self) -> bool:
        return self.thread.is_alive()

    def stop(self, join_timeout: float = 2.0) -> None:
        self.stop_event.set()
        self.thread.join(timeout=join_timeout)
        self.sidecar.close()


class Executor:
    """Thread-backed serverless fabric.

    ``bus`` is anything satisfying the :class:`~.bus.BusLike` seam — the
    in-process :class:`~.bus.MessageBus` or a :class:`~.transport.RemoteBus`
    speaking TCP to another host's bus: instances run identically either
    way, which is what makes :class:`RemoteWorker` a two-line wrapper."""

    def __init__(self, bus: MessageBus | BusLike):
        self._bus = bus
        self._instances: dict[str, InstanceHandle] = {}
        self._lock = threading.RLock()
        self._ids = itertools.count()

    # ------------------------------------------------------------------ run
    def start_instance(self, *, entity_kind: str, entity_name: str, owner: str,
                       logic: Callable, config: dict,
                       inputs: Sequence[str] = (), output: str | None = None,
                       db: Database | None = None, node: str | None = None,
                       queue_size: int = 256,
                       policy: DeliveryPolicy | None = None,
                       group: str | None = None,
                       key: str | None = None,
                       max_batch: int | None = None,
                       replay_from=None) -> InstanceHandle:
        """``policy`` (a typed :class:`~.delivery.DeliveryPolicy`) selects
        how this instance's input subscriptions share each subject:
        ``Group(name)`` joins the named bus queue group — all instances
        started under the same group form a single-delivery worker pool
        (scaling adds capacity, not copies); ``Keyed(group, field)``
        upgrades the pool so the named payload field is hashed and every
        message for a key reaches the same member (stateful workers scale
        without splitting a key's state).  The bare ``group=``/``key=``
        kwargs are the same thing spelled positionally and stay accepted
        here (this is runtime fabric, not the deprecated subscribe surface).
        ``max_batch`` bounds the mailbox burst handed to a batching-capable
        process (one exposing ``process_batch``) per pull; None defers to the
        process's own ``default_max_batch`` (1 = per-message pulls), which
        fused device units may autotune upward under sustained backlog.
        ``replay_from`` (durable inputs only) starts the input subscriptions
        on the subjects' logs — history is served before live delivery."""
        iid = f"{owner}/{entity_name}-{next(self._ids):04d}"
        stop_event = threading.Event()
        sidecar = Sidecar(iid, self._bus, inputs=inputs, output=output,
                          queue_size=queue_size, policy=policy, group=group,
                          key=key, replay_from=replay_from)

        handle = InstanceHandle(
            instance_id=iid, entity_kind=entity_kind, entity_name=entity_name,
            owner=owner, config=dict(config), sidecar=sidecar,
            thread=None, stop_event=stop_event, node=node)  # type: ignore[arg-type]

        runner = self._make_runner(handle, logic, db, max_batch)
        thread = threading.Thread(target=runner, name=iid, daemon=True)
        handle.thread = thread
        with self._lock:
            self._instances[iid] = handle
        thread.start()
        return handle

    def _make_runner(self, handle: InstanceHandle, logic: Callable,
                     db: Database | None,
                     max_batch: int | None = None) -> Callable[[], None]:
        sidecar, stop_event = handle.sidecar, handle.stop_event

        def run() -> None:
            try:
                if is_sdk_style(logic):
                    dx = DataX(sidecar, handle.config, db=db, stop_event=stop_event)
                    logic(dx)
                    return
                ctx = LogicContext(handle.config, db=db,
                                   instance_id=handle.instance_id,
                                   stop_event=stop_event)
                made = logic(ctx)
                if handle.entity_kind == "driver":
                    self._drive_source(made, sidecar, stop_event)
                else:
                    self._pump(made, sidecar, stop_event,
                               sink=handle.entity_kind == "actuator",
                               max_batch=max_batch)
            except Exception:
                handle.crashed = True
                handle.crash_info = traceback.format_exc()
            else:
                handle.completed = True

        return run

    @staticmethod
    def _drive_source(made: Any, sidecar: Sidecar,
                      stop_event: threading.Event) -> None:
        """Drivers: iterate a generator (or poll a callable) and emit."""
        if callable(made) and not hasattr(made, "__next__"):
            # callable driver: poll until it returns None or stop
            while not stop_event.is_set():
                t0 = time.monotonic()
                payload = made()
                if payload is None:
                    return
                sidecar.emit(payload)
                sidecar.record_processing(time.monotonic() - t0)
            return
        for payload in made:
            if stop_event.is_set():
                return
            if payload is None:
                continue
            sidecar.emit(payload)
            sidecar.record_processing(0.0)

    @staticmethod
    def _pump(process: Callable, sidecar: Sidecar, stop_event: threading.Event,
              sink: bool, max_batch: int | None = None) -> None:
        """AUs/actuators: pull → business logic → (emit).

        A process exposing ``process_batch(stream, [payloads]) ->
        [out | None, ...]`` (fused device units) switches the pump to
        drain-a-burst mode: each pull takes everything queued up to
        ``max_batch`` (the ``.scaled(max_batch=)`` knob, falling back to the
        process's own ``default_max_batch``) and hands the whole burst to one
        batched call.  A shallow mailbox yields 1-message bursts routed
        through the plain per-message path, so idle latency is unchanged —
        batching only engages when there is a backlog to amortize.
        """
        if not callable(process):
            raise TypeError("AU/actuator factory must return a callable process fn")
        warm = getattr(process, "warmup", None)
        if warm is not None and not stop_event.is_set():
            # fused device units expose .warmup to jit-compile ahead of the
            # first real message; best-effort (a failure just means the first
            # message pays the compile or falls back to the host chain), and
            # recorded separately so compile time never skews the latency
            # EWMA that drives straggler replacement
            t0 = time.monotonic()
            with contextlib.suppress(Exception):
                warm()
            sidecar.record_warmup(time.monotonic() - t0)
        sidecar.attach_process_stats(getattr(process, "stats", None))
        batch_fn = getattr(process, "process_batch", None)
        # a process marked ``wants_headers`` receives the message headers —
        # the durable-log offset rides there, which is how exactly-once
        # keyed stages pair each update with its log position
        wants_headers = bool(getattr(process, "wants_headers", False))
        if max_batch is None:
            max_batch = int(getattr(process, "default_max_batch", 1) or 1)
        burst = max(1, max_batch) if batch_fn is not None else 1
        # a process may autotune its own ceiling upward under sustained
        # backlog (fused device units expose current_max_batch); re-read it
        # per pull so deeper bursts engage without restarting the instance.
        # An explicit .scaled(max_batch=) stays authoritative: the process
        # only exposes the hook when the stream declared no ceiling.
        tuned = getattr(process, "current_max_batch", None) \
            if batch_fn is not None else None
        timings = sidecar.timings
        def emit_outs(outs) -> None:
            if sink:
                return
            with span("datax.pump.emit", timings, "emit_s"):
                for out in outs:
                    if out is None:
                        continue
                    for payload in (out if isinstance(out, list) else [out]):
                        sidecar.emit(payload)

        def account(t0: float, total: int, done: int) -> None:
            dt = (time.monotonic() - t0) / total
            for i in range(total):
                sidecar.record_processing(dt, ok=i < done)

        while not stop_event.is_set():
            if tuned is not None:
                try:
                    burst = max(1, int(tuned()))
                except Exception:
                    tuned = None
            with span("datax.pump.pull", timings, "pull_s"):
                if burst > 1:
                    got = sidecar.next_batch(burst, timeout=0.1)
                else:
                    one = sidecar.next(timeout=0.1)
                    got = None if one is None else (one[0], [one[1]])
            if got is None:
                continue
            stream, msgs = got
            # the burst's spans share the first message's seq
            with span("datax.pump.burst", seq=msgs[0].seq):
                t0 = time.monotonic()
                try:
                    if len(msgs) == 1:
                        if wants_headers:
                            outs = [process(stream, msgs[0].payload,
                                            headers=msgs[0].headers)]
                        else:
                            outs = [process(stream, msgs[0].payload)]
                    else:
                        outs = batch_fn(stream, [m.payload for m in msgs])
                except BatchInterrupted as bi:
                    # a poison message partway through a burst: the
                    # successful prefix still flows downstream; only the
                    # poison and the never-processed tail die with this
                    # instance — and they are accounted, not silently
                    # vanished (the reconciler restarts the instance; a
                    # group survivor inherits the rest of the mailbox)
                    sidecar.note_lost(stream, len(msgs) - len(bi.results))
                    account(t0, len(msgs), len(bi.results))
                    emit_outs(bi.results)
                    raise
                except Exception:
                    # poison message: the in-flight messages die with this
                    # instance and, under single delivery, the popped copies
                    # were the ONLY ones — account them on the subject's
                    # lost stat
                    sidecar.note_lost(stream, len(msgs))
                    account(t0, len(msgs), 0)
                    raise
                account(t0, len(msgs), len(msgs))
                emit_outs(outs)

    # ------------------------------------------------------------- lifecycle
    def stop_instance(self, instance_id: str) -> None:
        with self._lock:
            handle = self._instances.pop(instance_id, None)
        if handle is not None:
            handle.stop()

    def instances_of(self, owner: str) -> list[InstanceHandle]:
        with self._lock:
            return [h for h in self._instances.values() if h.owner == owner]

    def all_instances(self) -> list[InstanceHandle]:
        with self._lock:
            return list(self._instances.values())

    def get(self, instance_id: str) -> InstanceHandle | None:
        with self._lock:
            return self._instances.get(instance_id)

    def reap_dead(self) -> list[InstanceHandle]:
        """Remove finished/crashed instances; return them (reconciler restarts)."""
        with self._lock:
            dead = [h for h in self._instances.values()
                    if not h.thread.is_alive()]
            for h in dead:
                del self._instances[h.instance_id]
        for h in dead:
            h.sidecar.close()
        return dead

    def shutdown(self) -> None:
        with self._lock:
            handles = list(self._instances.values())
            self._instances.clear()
        for h in handles:
            h.stop()


# ---------------------------------------------------------------------------
# Cross-host workers
# ---------------------------------------------------------------------------

class RemoteWorker:
    """A worker process's attachment to a remote deployment: one
    :class:`~.transport.RemoteBus` connection plus a local :class:`Executor`
    running instances against it.

    The host process calls :meth:`~.operator.Operator.serve`; a worker
    process then does::

        worker = RemoteWorker("127.0.0.1:47000", peer="gpu-box-1")
        worker.start_instance(entity_kind="analytics_unit", ...,
                              inputs=("readings",), output="scores",
                              group="scores", key="sensor_id")

    and its instances join the host's queue groups / keyed rings as
    first-class members — the rendezvous ring hashes their stable
    subscription names, so cross-host partition hand-off and crashed-worker
    backlog re-homing behave exactly as in-process.  ``start_instance``
    takes the same kwargs as :meth:`Executor.start_instance`.
    """

    def __init__(self, address, *, peer: str = "", connect_timeout: float = 5.0,
                 **remote_kwargs):
        from .transport import RemoteBus
        self.bus = RemoteBus(address, peer=peer,
                             connect_timeout=connect_timeout, **remote_kwargs)
        self.executor = Executor(self.bus)

    def start_instance(self, **kwargs) -> InstanceHandle:
        """Run one instance locally, subscribed/publishing over the wire
        (same signature as :meth:`Executor.start_instance`)."""
        return self.executor.start_instance(**kwargs)

    def all_instances(self) -> list[InstanceHandle]:
        """Handles of every instance this worker is running."""
        return self.executor.all_instances()

    def metrics(self) -> dict:
        """Per-instance sidecar metrics, each carrying the federated
        ``transport`` block (connection state, frames, reconnects)."""
        return {h.instance_id: h.sidecar.metrics()
                for h in self.executor.all_instances()}

    def transport_stats(self) -> dict:
        """This worker's client-side connection counters."""
        return self.bus.transport_stats()

    def close(self) -> None:
        """Stop every instance (their unsubscribes re-home backlog to
        surviving members on the host), then drop the connection."""
        self.executor.shutdown()
        self.bus.close()


# ---------------------------------------------------------------------------
# Autoscaling policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScalePolicy:
    """Backlog/latency-driven scaling thresholds."""

    backlog_high: int = 32        # scale up if backlog-per-instance exceeds this
    backlog_low: int = 2          # scale down if total backlog below this
    idle_s: float = 5.0           # and instances have been idle this long
    cooldown_s: float = 1.0       # min seconds between decisions per stream
    steal_streak: int = 3         # consecutive stealing decisions = straggler


class AutoScaler:
    """Decides instance counts from sidecar metrics (paper §4: metrics drive
    the auto-scaling process).

    Signals are **group-aggregate**: under queue-group (single) delivery the
    pool shares one logical queue split across member mailboxes, so a single
    replica's mailbox depth no longer reflects load — the scale-up test is the
    pool's TOTAL backlog against ``backlog_high × members`` (for broadcast
    replicas every mailbox holds the same messages, so the aggregate form is
    conservative-equivalent at N=1 and stricter above).  Nonzero mailbox drops
    since the last decision are a hard scale-up signal regardless of backlog:
    drops mean the pool is already losing data, not merely lagging.

    Keyed pools add a **per-partition** signal: hashing concentrates hot keys
    on single members, so the aggregate can look healthy while one partition
    (and therefore one member) is drowning.  The sidecar metrics carry the
    keyed groups' exact per-partition backlogs; any partition above
    ``backlog_high`` scales the pool up — more members re-spread the
    remaining partitions off the hot member (a single key can never split,
    but its neighbours can move away).

    Stealing pools add a **straggler** signal: work stealing masks a slow
    member's backlog (idle peers drain it), so the backlog signals above can
    look healthy while the pool quietly burns capacity compensating.  The
    groups' ``stolen`` counter still rising across ``steal_streak``
    consecutive decisions means the imbalance is structural, not a blip —
    scale up by one so the pool stops depending on theft to keep up.
    """

    def __init__(self, policy: ScalePolicy | None = None):
        self.policy = policy or ScalePolicy()
        self._last_decision: dict[str, float] = {}
        # per-instance drop watermarks: a replaced instance must not lower
        # the pool total and mask fresh drops on the survivors
        self._last_drops: dict[str, dict[str, int]] = {}
        # stolen-counter watermark + consecutive-rising streak per stream
        self._last_stolen: dict[str, int] = {}
        self._steal_streak: dict[str, int] = {}

    @staticmethod
    def _stolen_total(metrics: Sequence[Mapping]) -> int:
        """Pool-wide stolen-message/partition count across all groups.
        The counter lives on the group (every member's sidecar reports the
        same value), so take the max per group view, not the sum."""
        total = 0
        seen: dict[str, int] = {}
        for m in metrics:
            for subject, snap in (m.get("groups") or {}).items():
                seen[subject] = max(seen.get(subject, 0),
                                    int(snap.get("stolen", 0)))
        for v in seen.values():
            total += v
        return total

    @staticmethod
    def _hot_partition_backlog(metrics: Sequence[Mapping]) -> int:
        """Deepest per-partition backlog across the pool's keyed groups
        (0 when the pool is not keyed)."""
        worst = 0
        for m in metrics:
            if not m.get("key"):
                continue
            for snap in (m.get("groups") or {}).values():
                pb = snap.get("partition_backlog") or {}
                if pb:
                    worst = max(worst, max(pb.values()))
        return worst

    def decide(self, owner: str, handles: Sequence[InstanceHandle],
               min_instances: int, max_instances: int) -> int:
        """Return the desired instance count for ``owner``."""
        now = time.monotonic()
        cur = len(handles)
        if cur == 0:
            return max(min_instances, 1)
        last = self._last_decision.get(owner, 0.0)
        if now - last < self.policy.cooldown_s:
            return cur
        metrics = [h.sidecar.metrics() for h in handles]
        total_backlog = sum(m["backlog"] for m in metrics)
        hot_partition = self._hot_partition_backlog(metrics)
        prev_drops = self._last_drops.get(owner, {})
        drops = {m["instance"]: m["dropped"] for m in metrics}
        new_drops = any(d > prev_drops.get(iid, 0) for iid, d in drops.items())
        self._last_drops[owner] = drops
        all_idle = all(m["idle_s"] > self.policy.idle_s for m in metrics)
        stolen = self._stolen_total(metrics)
        if stolen > self._last_stolen.get(owner, 0):
            self._steal_streak[owner] = self._steal_streak.get(owner, 0) + 1
        else:
            self._steal_streak[owner] = 0
        self._last_stolen[owner] = stolen
        stealing_hard = (self._steal_streak.get(owner, 0)
                         >= self.policy.steal_streak)

        desired = cur
        if (total_backlog > self.policy.backlog_high * cur or new_drops
                or hot_partition > self.policy.backlog_high) \
                and cur < max_instances:
            desired = min(max_instances, cur * 2)
        elif stealing_hard and cur < max_instances:
            # sustained stealing = a structural straggler; one extra member
            # (not a doubling — the pool is keeping up, just inefficiently)
            desired = cur + 1
            self._steal_streak[owner] = 0
        elif total_backlog <= self.policy.backlog_low and all_idle \
                and cur > min_instances:
            desired = cur - 1
        if desired != cur:
            self._last_decision[owner] = now
        return desired
