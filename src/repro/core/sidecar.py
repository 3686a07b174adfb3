"""DataX Sidecar — per-instance data-plane manager + metrics (paper §4).

"The main role of the DataX Sidecar is to automatically manage data
communication (it manages the connection, subscriptions, and publishing to the
messages bus).  Also, DataX Sidecar monitors the health of the user's
application; it exposes ... metrics such as the systems resources utilization
and the number of messages received, dropped, and published."

One Sidecar is attached to every running instance.  It owns the bus
subscriptions and the publish path (business logic never touches the bus), and
keeps the counters that drive (a) autoscaling, (b) straggler detection, and
(c) the health checks the reconciler uses to restart dead instances.
"""
from __future__ import annotations

import threading
import time
from typing import Sequence

from .bus import BusLike, MessageBus, Subscription
from .delivery import DeliveryPolicy, ReplayFrom, policy_from_legacy
from .schema import Message


#: A fused unit's per-phase host seconds (``process.stats``), surfaced as
#: top-level metrics; the pump's own are in :attr:`Sidecar.timings`.
_PHASES = ("stack_s", "upload_s", "place_s", "program_s", "download_s",
           "unstack_s")


class Sidecar:
    """Connection + subscription + publish manager, with metrics.

    ``bus`` is any :class:`~.bus.BusLike` — the in-process bus or a
    :class:`~.transport.RemoteBus`; in the remote case the sidecar's
    :meth:`metrics` additionally carries the federated ``transport`` block
    (connection state, frames/bytes in/out, reconnects)."""

    def __init__(self, instance_id: str, bus: MessageBus | BusLike, *,
                 inputs: Sequence[str] = (), output: str | None = None,
                 token: str | None = None, queue_size: int = 256,
                 wire: bool = False, policy: DeliveryPolicy | None = None,
                 group: str | None = None,
                 key: str | None = None, replay_from=None):
        self.instance_id = instance_id
        self._bus = bus
        self._output = output
        # the sidecar is runtime fabric, not user surface: it carries the
        # (group, key) pair the Operator derived from the StreamSpec, or an
        # explicit typed policy, and always speaks the typed form to the bus
        policy = policy if policy is not None \
            else policy_from_legacy(group, key)
        self.policy = policy
        legacy = policy.legacy_args() if policy is not None \
            else (None, None, None)
        self.group, self.key = legacy[0], legacy[1]
        if isinstance(replay_from, ReplayFrom):
            replay_from = replay_from.start
        self.replay_from = replay_from
        self._token = token or bus.issue_token(
            instance_id, list(inputs) + ([output] if output else []))
        # policy: scaled instances of one entity join the same queue group
        # (Group) on every input subject — each message reaches exactly one
        # of them (a worker pool); Keyed upgrades the group so each key
        # sticks to one member; None keeps broadcast replicas.
        # replay_from starts each subscription on the (durable) subject's
        # log — the pump then serves history before live messages.
        self._subs: list[Subscription] = [
            bus.subscribe(s, token=self._token, maxsize=queue_size, wire=wire,
                          name=f"{instance_id}:{s}", policy=policy,
                          replay=ReplayFrom(replay_from)
                          if replay_from is not None else None)
            for s in inputs
        ]
        self._rr = 0  # round-robin cursor over input subscriptions
        self._lock = threading.Lock()
        # deploy-time datax-check findings for this instance's stream
        # (operator pushes them at spawn via note_diagnostics)
        self.diagnostics: list[dict] = []
        # metrics
        self.published = 0
        self.processed = 0
        self.errors = 0
        self.latency_ewma_s = 0.0     # business-logic processing latency
        self.warmup_s = 0.0           # one-off setup (jit compile) cost
        self.batches = 0              # next_batch() bursts handed out
        self.batch_msgs = 0           # messages delivered inside those bursts
        self.max_batch_seen = 0       # deepest single burst
        # seconds the pump spent pulling and emitting (its spans), and the
        # summed time messages waited between publish and being handed out
        self.timings = {"pull_s": 0.0, "emit_s": 0.0,
                        "mailbox_wait_s": 0.0, "mailbox_msgs": 0}
        self.started_at = time.monotonic()
        self.last_activity = self.started_at
        self._ewma_alpha = 0.2
        # counters owned by the business logic (e.g. a fused device unit's
        # device_fallbacks) — attached by the Executor, read by metrics()
        self._process_stats: dict | None = None

    # -- data plane (used by the SDK / runtime, not by business logic) -------
    def _pull(self, max_n: int, timeout: float | None
              ) -> tuple[str, list] | None:
        """The round-robin scan shared by :meth:`next` and
        :meth:`next_batch`: a fast non-blocking pass over every input, then
        a blocking wait on the round-robin head.  Returns
        ``(subject, [messages])`` (1 <= len <= max_n) or None."""
        if not self._subs or max_n < 1:
            return None
        n = len(self._subs)
        for i in range(n):
            sub = self._subs[(self._rr + i) % n]
            msgs = sub.next_batch(max_n, timeout=0)
            if msgs:
                self._rr = (self._rr + i + 1) % n
                return self._handed_out(sub.subject, msgs)
        if timeout == 0:
            return None
        sub = self._subs[self._rr % n]
        msgs = sub.next_batch(max_n, timeout=timeout)
        if not msgs:
            return None
        self._rr = (self._rr + 1) % n
        return self._handed_out(sub.subject, msgs)

    def _handed_out(self, subject: str, msgs: list) -> tuple[str, list]:
        """Stamp activity and add each message's wait since publish
        (``Message.ts``, the publisher's monotonic clock) to
        ``mailbox_wait_s``."""
        now = time.monotonic()
        self.last_activity = now
        t = self.timings
        t["mailbox_wait_s"] += sum(now - m.ts for m in msgs)
        t["mailbox_msgs"] += len(msgs)
        return (subject, msgs)

    def next(self, timeout: float | None = 0.1) -> tuple[str, Message] | None:
        """Round-robin poll across input subscriptions.

        Returns (stream_name, message) or None if nothing arrived in time.
        Mirrors the paper's SDK ``next()`` returning "the name of the stream
        and the message".
        """
        got = self._pull(1, timeout)
        return None if got is None else (got[0], got[1][0])

    def next_batch(self, max_n: int, timeout: float | None = 0.1
                   ) -> tuple[str, list[Message]] | None:
        """Round-robin burst pull: up to ``max_n`` messages from ONE input
        subscription in a single drain (``(stream_name, [messages])``).

        Blocking behaviour mirrors :meth:`next`, and a shallow mailbox
        yields a 1-message burst with unchanged latency.  Burst sizes are
        recorded (``batches`` / ``batch_msgs`` / ``max_batch_seen``) so the
        metrics surface shows how well batched execution is amortizing.
        """
        got = self._pull(max_n, timeout)
        if got is not None:
            self._note_batch(len(got[1]))
        return got

    def _note_batch(self, size: int) -> None:
        with self._lock:
            self.batches += 1
            self.batch_msgs += size
            if size > self.max_batch_seen:
                self.max_batch_seen = size
            self.last_activity = time.monotonic()

    def emit(self, payload: dict, headers: dict | None = None) -> None:
        if self._output is None:
            raise RuntimeError(f"instance {self.instance_id} has no output stream")
        self._bus.publish(self._output, payload, token=self._token,
                          headers=headers)
        with self._lock:
            self.published += 1
            self.last_activity = time.monotonic()

    # -- bookkeeping ----------------------------------------------------------
    def record_processing(self, latency_s: float, ok: bool = True) -> None:
        with self._lock:
            self.processed += 1
            if not ok:
                self.errors += 1
            a = self._ewma_alpha
            self.latency_ewma_s = (1 - a) * self.latency_ewma_s + a * latency_s

    def attach_process_stats(self, stats: dict | None) -> None:
        """Adopt a mutable counter dict owned by the business logic (a fused
        device unit exposes ``process.stats``) so logic-level counters —
        ``device_fallbacks`` above all — reach the REST metrics surface."""
        self._process_stats = stats

    def note_lost(self, subject: str, n: int = 1) -> None:
        """Report in-flight message destruction (poison message crashing the
        instance) to the bus, where it lands on the subject's ``lost`` stat."""
        self._bus.note_lost(subject, n)

    def record_warmup(self, seconds: float) -> None:
        """One-off setup cost (e.g. jit compile of a fused device chain) —
        surfaced as its own metric, excluded from the latency EWMA so the
        reconciler never mistakes compilation for straggling."""
        with self._lock:
            self.warmup_s = seconds
            self.last_activity = time.monotonic()

    # -- the REST-analog metrics endpoint (paper: sidecar exposes REST API) ---
    def _group_metrics(self) -> dict:
        """Per-input queue-group view: delivery lag (delivered vs drained —
        i.e. handed to the pool but not yet popped), reroutes, and for keyed
        groups the live partition assignment map + per-partition backlog.
        This is how group/partition state reaches the REST surface instead
        of living only in ``bus.stats()``."""
        out = {}
        for s in self._subs:
            snap = self._bus.group_info(s.subject, self.group)
            if snap is None:
                continue
            info = {
                "policy": snap["policy"],
                "members": len(snap["members"]),
                "delivered": snap["delivered"],
                "lag": snap["backlog"],       # delivered - drained
                "rerouted": snap["rerouted"],
                # work stealing: moves an idle member pulled from the
                # deepest mailbox, and denials (deep victim, nothing
                # eligible).  Sustained stealing marks a straggler — the
                # autoscaler reads these through the same snapshot.
                "steal_enabled": snap.get("steal_enabled", False),
                "stolen": snap.get("stolen", 0),
                "steal_denied": snap.get("steal_denied", 0),
            }
            if snap["policy"] == "keyed":
                info.update(key=snap["key"],
                            assignment=snap["assignment"],
                            partition_backlog=snap["partition_backlog"],
                            stolen_partitions=snap.get(
                                "stolen_partitions", {}))
            out[s.subject] = info
        return out

    def _durable_metrics(self) -> dict:
        """Per-subject durable-log catalog for every durable input/output
        (depth, segments, retention evictions, offsets) — the REST surface
        for the durability layer."""
        out = {}
        subjects = [s.subject for s in self._subs]
        if self._output is not None:
            subjects.append(self._output)
        for subject in subjects:
            log = self._bus.durable_log(subject)
            if log is not None and subject not in out:
                out[subject] = log.info()
        return out

    def _transport_metrics(self) -> dict | None:
        """Client-side wire counters when the bus is remote (None when the
        bus is in-process): per-peer connection state, frames/bytes in/out,
        and reconnect count — the federated half of docs/metrics.md's
        transport section."""
        stats = getattr(self._bus, "transport_stats", None)
        return stats() if callable(stats) else None

    def metrics(self) -> dict:
        received = sum(s.received for s in self._subs)
        dropped = sum(s.dropped for s in self._subs)
        backlog = sum(s.qsize() for s in self._subs)
        groups = self._group_metrics() if self.group else {}
        durable = self._durable_metrics()
        replaying = any(s.replaying for s in self._subs)
        replayed = sum(s.replayed for s in self._subs)
        replay_lag = max((s.replay_lag() for s in self._subs), default=0)
        deduped = sum(s.deduped for s in self._subs)
        with self._lock:
            stats = self._process_stats or {}
            return {
                "instance": self.instance_id,
                "group": self.group,
                "key": self.key,
                "received": received,
                "dropped": dropped,
                "published": self.published,
                "processed": self.processed,
                "errors": self.errors,
                "backlog": backlog,
                "groups": groups,
                "latency_ewma_s": self.latency_ewma_s,
                "warmup_s": self.warmup_s,
                "batches": self.batches,
                "batch_msgs": self.batch_msgs,
                "max_batch_seen": self.max_batch_seen,
                "avg_batch": (self.batch_msgs / self.batches
                              if self.batches else 0.0),
                # logic-owned counters (fused units): batched_bursts > 0 is
                # the signal that vmapped device batching actually engaged —
                # the sidecar-level batches/batch_msgs above count every
                # mailbox pull, including per-message degrades
                "device_fallbacks": int(stats.get("device_fallbacks", 0)),
                "device_demotions": int(stats.get("device_demotions", 0)),
                "unstackable_bursts": int(stats.get("unstackable_bursts", 0)),
                "batched_bursts": int(stats.get("batched_bursts", 0)),
                "batched_msgs": int(stats.get("batched_msgs", 0)),
                # host seconds per phase, summed over the unit's life (the
                # spans of the pump and of fused units: docs/metrics.md)
                **self.timings,
                **{k: float(stats.get(k, 0.0)) for k in _PHASES},
                # mesh execution surface (fused units on a multi-device
                # mesh): how many devices the unit's mesh spans (1 = no
                # mesh), how many bursts ran SPMD-partitioned across it,
                # how many device buffers were reused across a linked
                # exit/entry pair instead of re-uploading from host, how
                # many bursts were stacked into kept host buffers and how
                # many such buffers were allocated, and the autotuned
                # burst ceiling currently in force
                "mesh_devices": int(stats.get("mesh_devices", 1)),
                "sharded_bursts": int(stats.get("sharded_bursts", 0)),
                "sharded_retired": int(stats.get("sharded_retired", 0)),
                "resident_links": int(stats.get("resident_links", 0)),
                "stage_reuses": int(stats.get("stage_reuses", 0)),
                "stage_allocs": int(stats.get("stage_allocs", 0)),
                "max_batch_current": int(stats.get("max_batch_current", 0)),
                # durability surface: log catalogs per durable subject,
                # replay progress of this instance's subscriptions, and the
                # age of the newest exactly-once recovery snapshot (logic-
                # owned — keyed stateful stages stamp last_snapshot_ts)
                "durable": durable,
                "replaying": replaying,
                "replayed": replayed,
                "replay_lag": replay_lag,
                "deduped": deduped,
                "snapshots": int(stats.get("snapshots", 0)),
                "snapshot_age_s": (
                    time.time() - stats["last_snapshot_ts"]
                    if stats.get("last_snapshot_ts") else None),
                # federated transport view (remote buses only, else None)
                "transport": self._transport_metrics(),
                # deploy-time datax-check findings anchored at this
                # instance's stream (code + severity; full records on
                # Operator.diagnostics())
                "diagnostics": [{"code": d.get("code"),
                                 "severity": d.get("severity")}
                                for d in self.diagnostics],
                "uptime_s": time.monotonic() - self.started_at,
                "idle_s": time.monotonic() - self.last_activity,
            }

    def note_diagnostics(self, entries) -> None:
        """Attach deploy-time ``datax check`` findings (JSON dicts) for
        this instance's stream; surfaced in :meth:`metrics`."""
        self.diagnostics = [dict(e) for e in entries]

    def healthy(self, stall_timeout_s: float = 60.0) -> bool:
        m = self.metrics()
        if m["errors"] > 0 and m["processed"] == m["errors"]:
            return False  # every message errored
        return True

    def close(self) -> None:
        for s in self._subs:
            self._bus.unsubscribe(s)
        self._bus.revoke_token(self._token)
