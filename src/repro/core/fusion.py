"""Chain-fusion compiler pass + device executor (TPU adaptation).

DataX's promise is that the runtime "automatically sets up appropriate data
communication mechanisms" for the declared graph.  For HOST analytics units the
right mechanism is the message bus; for a *linear chain* of DEVICE-placement
AUs the right mechanism is no communication at all — the chain should be one
jitted program on the mesh, with interior hops as in-program values.

This module is the first real compiler pass between the fluent API and the
runtime.  It operates on the compiled v1 :class:`~.app.Application` spec graph
(so v1 spec-style apps benefit too):

1. **Segment detection** (:func:`plan_segments`) — maximal linear runs of
   streams whose AU is ``Placement.DEVICE``, single-input, stateless, and
   whose interior streams have exactly one consumer.  Fusion barriers:

   * ``.window`` / ``fuse`` combinators (stateful / multi-input — never
     DEVICE, so they stop a chain structurally);
   * multi-subscriber taps — an interior stream consumed by a second stream
     or a gadget must stay on the bus, so the segment splits there;
   * explicit taps (:meth:`StreamHandle.tap` / the ``taps`` argument) — the
     stream is promised to external subscribers and must remain a bus subject;
   * fixed instance counts > 1 (fusing would change scaling semantics).

2. **Collapse** (:func:`fuse_application`) — each segment of length >= 2 is
   replaced by one synthetic fused AU + one stream named after the segment
   exit.  Only the entry and exit edges touch the bus; interior subjects are
   never registered.  Synthetic combinator AUs orphaned by the collapse are
   garbage-collected; declared AUs stay in the catalog.

3. **Execution** (:func:`make_fused_logic`) — the fused AU's factory
   instantiates every stage factory (stage configs resolved at fusion time)
   and chains them in-process.  When jax is importable, *every* stage
   carries a ``pure_fn``, and the backend warrants it (:data:`JIT_MODE` —
   accelerators by default), the stages are composed into a single
   ``jax.jit`` program (:func:`repro.kernels.ops.jit_chain`); payloads move
   to the device once at segment entry and back once at exit.  The device
   path degrades transparently: no jax, a CPU-only backend, a stage without
   a pure_fn, or a payload/stage that fails to trace (impure, non-numeric
   fields) → the same chain runs host-composed, bit-identical to per-hop bus
   execution, still with zero interior bus hops.  A payload-local problem
   (a single non-numeric message) falls back for that message only; the
   device program stays live (``device_fallbacks`` counts them in sidecar
   metrics) — only a genuine program failure demotes the unit permanently,
   and never unseen: ``device_demotions`` counts it in sidecar metrics and
   a warning names the failure.

4. **Batched execution** — under backlog the Executor drains a mailbox
   burst and hands it to ``process_batch``: the whole burst is stacked
   field-wise (one host->device transfer), run through ONE vmapped program
   (:func:`repro.kernels.ops.jit_chain_batched`, per-message keep mask for
   predicated filters) and unstacked once — amortizing the per-message XLA
   dispatch that makes per-message jit slower than the host chain on CPU.
   Bursts are bounded by ``.scaled(max_batch=)`` (default
   :data:`DEFAULT_MAX_BATCH`) and padded to power-of-two sizes so at most
   log2(max_batch) batch shapes compile; ragged / mixed-shape / non-numeric
   bursts degrade per-message, bit-identical to the host chain.

5. **Mesh-sharded execution** — when more than one device is visible
   (:func:`fusion_mesh` — a 1-D ``data`` mesh over ``jax.local_devices()``,
   disable with ``DATAX_FUSION_MESH=0``) and the padded burst divides the
   mesh, the burst runs through the SPMD-partitioned program instead
   (:func:`repro.kernels.ops.jit_chain_sharded`): each field is committed
   to a ``NamedSharding`` whose leading burst dim splits over the data
   axis — trailing dims follow the stream schema's per-field
   :class:`~.schema.ShardSpec` hints via
   :func:`repro.distributed.sharding.burst_spec` — so every device
   computes its slice of the burst.  vmap rows are independent, so the
   sharded path is bit-identical to the single-device batched program; any
   indivisible burst stays on the single-device path, and a sharded-program
   failure retires the sharded program for the unit (``sharded_retired``
   in sidecar metrics, plus a warning).  Two ride-alongs:

   * **device residency** — a segment whose exit feeds ANOTHER fused
     segment's entry emits its array fields as :class:`ResidentArray`
     rows (plain ndarrays that remember the stacked device burst they
     came from); when the downstream unit re-stacks an intact burst it
     reuses the device array directly and the linked hop pays zero
     host->device transfer (``resident_links`` in sidecar metrics);
   * **burst autotune** — streams that declare no ``max_batch`` start at
     :data:`DEFAULT_MAX_BATCH` and double their ceiling (up to
     :data:`AUTOTUNE_MAX_BATCH`) after :data:`AUTOTUNE_STREAK` consecutive
     ceiling-filling bursts — sustained full occupancy means the mailbox
     is backlogged and a bigger program amortizes further.  The tuner also
     runs DOWN: :data:`AUTOTUNE_DOWN_STREAK` consecutive bursts slower
     than :data:`AUTOTUNE_BUDGET_S` halve the ceiling (floor 1) — past the
     device's sweet spot a bigger burst only stretches per-message
     latency.  The Executor re-reads the tuned ceiling
     (``process.current_max_batch``) each pump.

Upgrading an individual stage AU after fusion does not cascade into already-
deployed fused units (the fused AU snapshots stage logic at build time);
redeploy the app to pick up new stage versions.
"""
from __future__ import annotations

import dataclasses
import enum
import logging
import time
from typing import Any, Callable, Iterable, Mapping, Sequence

import numpy as np

from .app import Application
from .entities import AnalyticsUnitSpec, Placement, StreamSpec
from .schema import StreamSchema
from .sdk import BatchInterrupted, LogicContext, is_sdk_style

try:  # the pass (host-composed path) must work without jax installed
    import jax  # noqa: F401
    _HAS_JAX = True
except Exception:  # pragma: no cover - exercised via monkeypatch in tests
    _HAS_JAX = False

_log = logging.getLogger(__name__)

#: When the fused unit uses the jitted device program vs the host-composed
#: chain (both are single-microservice, zero interior bus hops):
#:
#: * ``"auto"``   — jit only on accelerator backends (tpu/gpu).  On CPU the
#:   per-message XLA dispatch + host<->device sync costs more than the numpy
#:   math it replaces (same reasoning as kernels/ops.py interpret mode), so
#:   the host chain IS the optimal lowering there.
#: * ``"always"`` — jit whenever jax + pure stages allow (tests use this to
#:   prove jit/host bit-identity on CPU).
#: * ``"never"``  — host-composed chain only.
#:
#: Overridable via the DATAX_FUSION_JIT environment variable.
JIT_MODE = "auto"

#: Default burst ceiling for a fused unit's batched execution when the stream
#: declares no ``max_batch`` of its own (``.scaled(max_batch=)``).  Each
#: mailbox pull drains up to this many queued messages into one vmapped
#: program call; bursts are padded up to the next power of two so at most
#: log2(max_batch) batch shapes ever compile (no retrace storm).
DEFAULT_MAX_BATCH = 32

#: Ceiling for the burst autotuner.  A stream that declares no ``max_batch``
#: starts at :data:`DEFAULT_MAX_BATCH` and doubles under sustained full
#: occupancy — but never beyond this, bounding both per-burst latency and
#: the number of compiled batch shapes (log2(AUTOTUNE_MAX_BATCH) total).
AUTOTUNE_MAX_BATCH = 256

#: Consecutive ceiling-filling device bursts before the autotuner doubles
#: ``max_batch`` — one full burst can be a blip; a streak means the mailbox
#: is genuinely backlogged at the current ceiling.
AUTOTUNE_STREAK = 4

#: Per-burst drain-latency budget for the autotuner's DOWN direction.  A
#: bigger ceiling amortizes dispatch, but past the device's sweet spot it
#: only stretches the burst: every message in the burst then waits the whole
#: burst's wall time.  Bursts slower than this budget count against the
#: ceiling; :data:`AUTOTUNE_DOWN_STREAK` of them in a row halve it (one slow
#: burst can be a GC pause or a recompile — a streak is the ceiling's fault).
AUTOTUNE_BUDGET_S = 0.25

#: Consecutive over-budget device bursts before the autotuner halves the
#: ceiling (floor 1; pad shapes stay powers of two).
AUTOTUNE_DOWN_STREAK = 4


def jax_available() -> bool:
    """Gate for the jitted path (module-level so tests can monkeypatch)."""
    return _HAS_JAX


_MESH_CACHE: list = []  # memo cell: [Mesh | None] once resolved


def fusion_mesh():
    """The device mesh fused programs shard over, or None.

    A 1-D ``("data",)`` :class:`jax.sharding.Mesh` spanning every locally
    visible device — built once and cached.  None (single-device semantics)
    when jax is unavailable, when only one device is visible, or when
    ``DATAX_FUSION_MESH=0`` disables sharding outright.  CI simulates a
    multi-device host with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    import os
    if os.environ.get("DATAX_FUSION_MESH", "1") in ("0", "off", "never"):
        return None
    if not jax_available():
        return None
    if not _MESH_CACHE:
        import jax
        devices = jax.local_devices()
        if len(devices) > 1:
            from jax.sharding import Mesh
            _MESH_CACHE.append(Mesh(np.array(devices), ("data",)))
        else:
            _MESH_CACHE.append(None)
    return _MESH_CACHE[0]


def mesh_axis_names() -> tuple:
    """Axis names of the active fusion mesh (empty when single-device).

    :meth:`~.dsl.App.build` unions these with the architectural axis
    vocabulary (:data:`~.schema.KNOWN_MESH_AXES`) when validating
    :class:`~.schema.ShardSpec` hints."""
    mesh = fusion_mesh()
    return tuple(mesh.axis_names) if mesh is not None else ()


def _want_jit() -> bool:
    import os
    mode = os.environ.get("DATAX_FUSION_JIT", JIT_MODE)
    if mode == "always":
        return True
    if mode == "never":
        return False
    import jax
    return jax.default_backend() not in ("cpu",)


@dataclasses.dataclass(frozen=True)
class FusedStage:
    """One folded-in hop of a fused segment."""

    au_name: str                  # stage AU (code entity) name
    stream_name: str              # the stream this stage produced pre-fusion
    factory: Callable             # the stage AU's logic factory
    config: Mapping[str, Any]     # resolved (schema-validated) stage config
    kind: str                     # "map" | "filter" | "au"
    pure_fn: Callable | None      # payload fn for jit composition, if pure


# ---------------------------------------------------------------------------
# Segment detection
# ---------------------------------------------------------------------------

class BarrierReason(enum.Enum):
    """Why a stream stops (or never joins) a fused DEVICE segment.

    The fusion pass used to decide barriers inline and throw the reason
    away; now every decision point returns one of these members so both
    :func:`plan_segments` and the ``DX201`` fusion-explainability rule in
    :mod:`repro.core.analyze` consume the *same* data — the explanation can
    never drift from the behavior.  ``str(reason)`` / ``reason.explain``
    give the operator-facing sentence.
    """

    #: The stream's AU is not ``Placement.DEVICE`` (host stages run on the bus).
    NOT_DEVICE = "not-device"
    #: The AU declares ``stateful=True`` — fused programs must be pure.
    STATEFUL = "stateful"
    #: The AU is itself a fused unit; never re-fuse one.
    FUSED_UNIT = "fused-unit"
    #: The AU's logic owns its own consume loop (SDK-style) — can't chain.
    SDK_STYLE = "sdk-style"
    #: The stream has more than one input subject (``fuse`` combinators etc.).
    MULTI_INPUT = "multi-input"
    #: ``fixed_instances > 1`` — fusing would change scaling semantics.
    FIXED_INSTANCES = "fixed-instances"
    #: The upstream subject has >1 consumer (or none); it must stay on the bus.
    MULTI_SUBSCRIBER = "multi-subscriber"
    #: The upstream subject is ``.tap()``-promised to external subscribers.
    TAPPED = "tapped"
    #: The upstream subject is durable; its log only fills on real publishes.
    DURABLE = "durable"
    #: The consumer replays history (``replay_from``); folding it mid-segment
    #: would re-anchor the replay onto the segment entry's subject.
    REPLAY = "replay"
    #: The keyed consumer re-partitions on its input (different key field, or
    #: a keyed consumer of an unkeyed stage).
    REPARTITION = "repartition"

    @property
    def explain(self) -> str:
        """One operator-facing sentence for this barrier."""
        return _BARRIER_EXPLANATIONS[self]

    def __str__(self) -> str:  # noqa: D105 - delegate to the explanation
        return f"{self.name}: {self.explain}"


_BARRIER_EXPLANATIONS: dict[BarrierReason, str] = {
    BarrierReason.NOT_DEVICE:
        "the stage is not DEVICE-placed, so it runs on the bus",
    BarrierReason.STATEFUL:
        "the stage declares stateful=True and fused programs must be pure",
    BarrierReason.FUSED_UNIT:
        "the stage is already a fused unit and is never re-fused",
    BarrierReason.SDK_STYLE:
        "the stage's logic owns its own consume loop and cannot be chained",
    BarrierReason.MULTI_INPUT:
        "the stage consumes more than one input subject",
    BarrierReason.FIXED_INSTANCES:
        "fixed_instances > 1 — fusing would change scaling semantics",
    BarrierReason.MULTI_SUBSCRIBER:
        "the upstream subject has more than one consumer (or none) and must "
        "stay on the bus",
    BarrierReason.TAPPED:
        "the upstream subject is .tap()-promised to external subscribers",
    BarrierReason.DURABLE:
        "the upstream subject is durable; its append-only log only fills if "
        "publishes hit the bus",
    BarrierReason.REPLAY:
        "the consumer replays history from its own input subject's log",
    BarrierReason.REPARTITION:
        "the keyed consumer re-partitions on its input (key differs from the "
        "upstream's, or the upstream is unkeyed)",
}


def consumer_counts(app: Application) -> dict[str, int]:
    """How many streams + gadgets consume each subject of ``app``."""
    counts: dict[str, int] = {}
    for s in app.streams:
        for i in s.inputs:
            counts[i] = counts.get(i, 0) + 1
    for g in app.gadgets:
        for i in g.inputs:
            counts[i] = counts.get(i, 0) + 1
    return counts


def stream_barrier(spec: StreamSpec,
                   aus: Mapping[str, AnalyticsUnitSpec]) -> BarrierReason | None:
    """Why ``spec`` can never be a fused-segment stage (None = fusible).

    These are properties of the stream/AU alone; :func:`edge_barrier` adds
    the edge-level reasons that depend on the upstream subject.
    """
    au = aus.get(spec.analytics_unit)
    if au is None or au.placement is not Placement.DEVICE:
        return BarrierReason.NOT_DEVICE
    if au.fused_stages:                  # never re-fuse a fused unit
        return BarrierReason.FUSED_UNIT
    if au.stateful:
        return BarrierReason.STATEFUL
    if is_sdk_style(au.logic):           # owns its own loop — can't chain
        return BarrierReason.SDK_STYLE
    if len(spec.inputs) != 1:
        return BarrierReason.MULTI_INPUT
    if spec.fixed_instances not in (None, 1):
        return BarrierReason.FIXED_INSTANCES
    return None


def edge_barrier(upstream: StreamSpec, nxt: StreamSpec,
                 aus: Mapping[str, AnalyticsUnitSpec], *,
                 consumers: Mapping[str, int],
                 taps: Iterable[str] = ()) -> BarrierReason | None:
    """Why ``nxt`` cannot extend a fused segment through ``upstream``.

    Returns None when the edge fuses.  ``consumers`` is
    :func:`consumer_counts` of the application; ``taps`` the promised
    subjects.  Subsumes :func:`stream_barrier` of ``nxt``.
    """
    if upstream.name in taps:
        # promised to external subscribers — must remain a bus subject
        return BarrierReason.TAPPED
    if consumers.get(upstream.name, 0) != 1:
        return BarrierReason.MULTI_SUBSCRIBER
    if upstream.durable:
        # a durable interior stream is a promise just like a tap: its
        # append-only log only fills if publishes hit the bus subject,
        # so it must stay a segment boundary
        return BarrierReason.DURABLE
    reason = stream_barrier(nxt, aus)
    if reason is not None:
        return reason
    if nxt.replay_from is not None:
        # a replaying consumer starts on its OWN input subjects' logs;
        # folding it mid-segment would re-anchor the replay onto the
        # segment entry's subject.  It may still head its own segment
        # (the fused unit inherits the entry's replay_from).
        return BarrierReason.REPLAY
    if nxt.delivery == "keyed" and not (upstream.delivery == "keyed"
                                        and upstream.key == nxt.key):
        # a keyed consumer re-partitions on ITS input.  If the chain is
        # uniformly keyed on the SAME field (the DSL propagates .key_by
        # through stateless stages), the fused unit inherits the entry's
        # key policy and hashes once at entry — equivalent to per-stage
        # hashing as long as interior stages don't rewrite the key
        # field's VALUE (rewriting it while keeping the field in the
        # schema re-partitions mid-chain in the unfused graph; keep such
        # a stage out of the device chain or .tap() it).  A different
        # key field (or a keyed consumer of an unkeyed stage) is a
        # genuine re-partition point: the interior stream must stay a
        # bus subject (segment barrier).  Pairwise same-key induction
        # keeps every fused segment uniformly keyed back to its entry.
        return BarrierReason.REPARTITION
    return None


def _fusible(spec: StreamSpec, aus: Mapping[str, AnalyticsUnitSpec]) -> bool:
    return stream_barrier(spec, aus) is None


def plan_segments(app: Application,
                  taps: Iterable[str] = ()) -> list[list[StreamSpec]]:
    """Maximal linear DEVICE segments, in topological order of their entries.

    Every returned segment has length >= 2 (a single DEVICE stream gains
    nothing from fusion — it already is one microservice).
    """
    taps = set(taps)
    aus = {a.name: a for a in app.analytics_units}
    streams = {s.name: s for s in app.streams}
    consumers = consumer_counts(app)

    def extendable(upstream: StreamSpec) -> StreamSpec | None:
        """The unique fusible successor of ``upstream``, or None (barrier)."""
        nxt = next((s for s in app.streams if upstream.name in s.inputs), None)
        if nxt is None:
            return None  # consumed only by gadgets / external subscribers
        if edge_barrier(upstream, nxt, aus,
                        consumers=consumers, taps=taps) is not None:
            return None
        return nxt

    segments: list[list[StreamSpec]] = []
    in_segment: set[str] = set()
    for spec in app.streams:  # declaration order is topological per validate()
        if spec.name in in_segment or not _fusible(spec, aus):
            continue
        # head check: the producer of our input must not absorb us
        prev = streams.get(spec.inputs[0])
        if prev is not None and _fusible(prev, aus) \
                and extendable(prev) is spec:
            continue  # interior of a segment headed earlier
        segment = [spec]
        while True:
            nxt = extendable(segment[-1])
            if nxt is None:
                break
            segment.append(nxt)
        if len(segment) >= 2:
            segments.append(segment)
            in_segment.update(s.name for s in segment)
    return segments


# ---------------------------------------------------------------------------
# Device / host chain execution
# ---------------------------------------------------------------------------

def _to_device(payload: Mapping[str, Any]) -> dict:
    """Payload -> jax arrays.  Raises on non-numeric fields (caller falls
    back to the host chain)."""
    import jax.numpy as jnp
    out = {}
    for k, v in payload.items():
        if isinstance(v, (str, bytes, dict, list, tuple)):
            raise TypeError(f"field {k!r} ({type(v).__name__}) is not "
                            f"device-representable")
        out[k] = jnp.asarray(v)
    return out


def _from_device(payload: Mapping[str, Any],
                 like: Mapping[str, Any]) -> dict:
    """Device arrays -> host values, mirroring what the same stage fns
    produce on numpy inputs (the host/unfused path is ground truth, and the
    two must stay interchangeable):

    * 0-d results of a field that entered as a python scalar -> python
      scalar (pass-through/arithmetic identity);
    * any other 0-d result (reductions, new fields) -> numpy scalar, exactly
      like a numpy reduction — NOT ``.item()``, which would let the jitted
      path accept payloads (e.g. against a ``FieldSpec("float")``) that the
      host path and per-hop bus execution reject;
    * everything else -> ndarray.
    """
    out = {}
    for k, v in payload.items():
        arr = np.asarray(v)
        if arr.ndim == 0:
            src = like.get(k)
            if src is not None and not isinstance(src, (np.ndarray, np.generic)):
                out[k] = arr.item()
            else:
                out[k] = arr[()]
        else:
            out[k] = arr
    return out


def _round_up_pow2(n: int) -> int:
    """Canonical (power-of-two) batch size for a burst of ``n`` messages.

    The jitted batch program retraces per input shape; rounding every burst
    up to the next power of two bounds the set of compiled batch shapes to
    log2(max_batch) instead of one per distinct backlog depth."""
    return 1 << max(0, n - 1).bit_length()


class ResidentArray(np.ndarray):
    """A host ndarray that remembers the device burst it was unstacked from.

    Fused segments whose exit feeds ANOTHER fused segment's entry emit
    their array fields as ResidentArrays: to every host-side consumer
    (schema validation, taps, wire transport) this is a plain numpy array,
    but it additionally holds the stacked device array it is row
    ``_datax_row`` of (``_datax_dev``).  When the downstream fused unit
    stacks a burst whose rows are exactly that still-resident device burst,
    :func:`_to_device_batched` hands the device array straight back to the
    next program — the linked hop pays zero host->device transfer
    (``resident_links`` in sidecar metrics).
    """

    _datax_dev: Any = None
    _datax_row: int = -1

    def __array_finalize__(self, obj):
        # Residency is NEVER inherited by views, slices, or copies: a
        # derived array is not the row the device burst holds, so it must
        # not claim the link.  wrap() is the only residency source.
        self._datax_dev = None
        self._datax_row = -1

    @classmethod
    def wrap(cls, row: np.ndarray, dev: Any, index: int) -> "ResidentArray":
        """Tag host ``row`` as row ``index`` of device array ``dev``."""
        out = np.asarray(row).view(cls)
        out._datax_dev = dev
        out._datax_row = index
        return out


def _resident_burst(rows: Sequence[Any], pad_to: int):
    """The shared device array behind a burst of ResidentArray rows, or None.

    Reuse demands an INTACT burst: every row resident, all from the same
    device array, indices exactly 0..N-1 (a filtered or reordered burst
    skips indices), full-row shapes, and the producer's padded batch equal
    to the consumer's ``pad_to`` (vmap rows are independent, so the
    producer's pad rows — repeats of its last input — are computed and
    discarded exactly like pad rows the consumer would have stacked)."""
    first = rows[0]
    if not isinstance(first, ResidentArray) or first._datax_dev is None:
        return None
    dev = first._datax_dev
    if getattr(dev, "shape", (0,))[0] != pad_to:
        return None
    for i, r in enumerate(rows):
        if (not isinstance(r, ResidentArray) or r._datax_dev is not dev
                or r._datax_row != i or r.shape != dev.shape[1:]):
            return None
    return dev


def _to_device_batched(payloads: Sequence[Mapping[str, Any]],
                       pad_to: int, stats: dict | None = None) -> dict:
    """Stack N payloads field-wise into one leading-batch-dim device payload.

    Raises TypeError on heterogeneous field sets, non-numeric fields, or
    ragged/mixed shapes-dtypes across the burst — the caller degrades that
    burst to per-message execution, bit-identical to the host chain.  Tails
    shorter than ``pad_to`` are padded by repeating the last row (the pad
    rows' outputs are discarded) so batch shapes stay canonical.

    Fields whose rows form an intact :class:`ResidentArray` burst skip the
    stack + transfer entirely and reuse the upstream device array
    (counted in ``stats['resident_links']`` when a stats dict is given)."""
    import jax.numpy as jnp
    keys = payloads[0].keys()
    for p in payloads[1:]:
        if p.keys() != keys:
            raise TypeError("burst payloads carry different field sets")
    out = {}
    for k in keys:
        resident = _resident_burst([p[k] for p in payloads], pad_to)
        if resident is not None:
            out[k] = resident
            if stats is not None:
                stats["resident_links"] += 1
            continue
        rows = []
        for p in payloads:
            v = p[k]
            if isinstance(v, (str, bytes, dict, list, tuple)) or v is None:
                raise TypeError(f"field {k!r} ({type(v).__name__}) is not "
                                f"device-representable")
            arr = np.asarray(v)
            if arr.dtype == object:
                raise TypeError(f"field {k!r} is not device-representable")
            rows.append(arr)
        first = rows[0]
        if any(r.shape != first.shape or r.dtype != first.dtype
               for r in rows[1:]):
            raise TypeError(f"field {k!r}: ragged shapes/dtypes across burst")
        if len(rows) < pad_to:
            rows.extend(rows[-1:] * (pad_to - len(rows)))
        out[k] = jnp.asarray(np.stack(rows))
    return out


def _from_device_batched(stacked: Mapping[str, Any],
                         likes: Sequence[Mapping[str, Any]],
                         resident: bool = False) -> list[dict]:
    """Stacked device results -> one host payload per (unpadded) message.

    One device->host transfer per FIELD for the whole burst — that single
    materialization is where batching beats per-message ``_from_device`` —
    then each row follows the exact scalar-typing rules of
    :func:`_from_device` against its own entry payload.

    With ``resident=True`` (segments feeding another fused segment) array
    rows come back as :class:`ResidentArray`, pinning the stacked device
    result so the downstream unit can reuse it without re-uploading."""
    host = {k: np.asarray(v) for k, v in stacked.items()}
    outs = []
    for i, like in enumerate(likes):
        p = {}
        for k, arr in host.items():
            row = arr[i]
            if row.ndim == 0:
                src = like.get(k)
                if src is not None and not isinstance(src, (np.ndarray,
                                                            np.generic)):
                    p[k] = row.item()
                else:
                    p[k] = row[()]
            elif resident:
                # the copy below intentionally does NOT apply: residency
                # trades keeping the device burst alive for a free re-entry
                # on the linked hop
                p[k] = ResidentArray.wrap(np.array(row), stacked[k], i)
            else:
                # copy out of the stacked block: a view would keep the whole
                # pad_to-sized burst alive for as long as ANY downstream
                # consumer holds one message of it
                p[k] = np.array(row)
        outs.append(p)
    return outs


def make_fused_logic(stages: Sequence[FusedStage],
                     entry_schema: StreamSchema | None,
                     max_batch: int | None = None,
                     resident: bool = False) -> Callable:
    """Factory for the fused AU: chain every stage in one instance.

    The returned factory honours the normal AU contract
    (``factory(ctx) -> process(stream, payload)``) so the Executor runs a
    fused unit exactly like any other microservice; additionally ``process``
    exposes the batched-execution surface the Executor's drain-a-burst mode
    keys on — ``process_batch`` (whole mailbox burst -> one vmapped program
    call; mesh-sharded when :func:`fusion_mesh` is live and the padded
    burst divides it), ``default_max_batch``, ``current_max_batch`` (the
    autotuned ceiling, present only when the stream declared no
    ``max_batch`` of its own) and a ``stats`` counter dict
    (``device_fallbacks`` / ``device_demotions`` / ``batched_bursts`` /
    ``batched_msgs`` / ``sharded_bursts`` / ``sharded_retired`` /
    ``resident_links`` / ``mesh_devices`` / ``max_batch_current``).
    ``resident=True`` marks a segment whose exit feeds another fused
    segment: its array outputs stay device-resident
    (:class:`ResidentArray`) for the linked hop.
    """

    def fused_factory(ctx):
        procs = []
        for st in stages:
            sctx = LogicContext(dict(st.config), db=ctx.db,
                                instance_id=ctx.instance_id,
                                stop_event=getattr(ctx, "_stop", None))
            procs.append(st.factory(sctx))

        def host_chain(i: int, stream: str, payload: dict) -> list:
            if i == len(procs):
                return [payload]
            out = procs[i](stream, payload)
            if out is None:
                return []
            results = []
            for p in (out if isinstance(out, list) else [out]):
                results.extend(host_chain(i + 1, stages[i].stream_name, p))
            return results

        program = batched_program = mesh = None
        sprog = {"fn": None}  # sharded program; retired on lowering failure
        if jax_available() and _want_jit() \
                and all(st.pure_fn is not None for st in stages):
            from ..kernels.ops import jit_chain, jit_chain_batched
            chain = [(st.kind, st.pure_fn) for st in stages]
            program = jit_chain(chain)
            batched_program = jit_chain_batched(chain)
            mesh = fusion_mesh()
            if mesh is not None:
                from ..distributed.sharding import burst_spec
                from ..kernels.ops import jit_chain_sharded
                hints = (entry_schema.sharding_hints()
                         if entry_schema is not None else {})
                specs = {}
                if entry_schema is not None:
                    for fname, f in entry_schema.fields.items():
                        if f.kind == "device" and f.shape is not None \
                                and -1 not in f.shape:
                            # build against a divisible batch: the runtime
                            # gate below only routes divisible bursts here
                            specs[fname] = burst_spec(
                                mesh, mesh.size, f.shape, hints.get(fname))
                sprog["fn"] = jit_chain_sharded(chain, mesh, specs)
        ndev = mesh.size if mesh is not None else 1
        mode = {"device": program is not None}
        # device_fallbacks counts MESSAGES that ran on the host while the
        # device program stayed live (payload-local problems);
        # unstackable_bursts counts bursts that degraded to per-message
        # dispatch (ragged/mixed shapes) — those messages may still run on
        # the device one at a time, so they are not fallbacks.
        tune = {"cur": max_batch or DEFAULT_MAX_BATCH, "streak": 0,
                "slow": 0,
                "auto": max_batch is None and program is not None}
        stats = {"device_fallbacks": 0, "unstackable_bursts": 0,
                 "device_demotions": 0, "batched_bursts": 0,
                 "batched_msgs": 0, "sharded_bursts": 0,
                 "sharded_retired": 0, "resident_links": 0,
                 "mesh_devices": ndev, "max_batch_current": tune["cur"]}

        # A failing device program must not become host execution unseen:
        # each demotion / retirement is counted (sidecar metrics) and logged.
        def demote(exc: Exception) -> None:
            mode["device"] = False
            stats["device_demotions"] += 1
            _log.warning("fused unit %s: device program failed, running the "
                         "host chain from now on: %r",
                         stages[-1].stream_name, exc)

        def retire_sharded(exc: Exception) -> None:
            sprog["fn"] = None
            stats["sharded_retired"] += 1
            _log.warning("fused unit %s: mesh-sharded program failed, "
                         "single-device bursts from now on: %r",
                         stages[-1].stream_name, exc)

        def run_device(payload: dict) -> dict | None:
            dev, keep = program(_to_device(payload))
            if not bool(keep):
                return None
            return _from_device(dev, payload)

        def host_one(stream: str, payload: dict):
            out = host_chain(0, stream, payload)
            if not out:
                return None
            return out if len(out) > 1 else out[0]

        def process(stream: str, payload: dict):
            if mode["device"]:
                try:
                    dev = _to_device(payload)
                except Exception:
                    # conversion failures are ALWAYS payload problems
                    # (non-numeric field -> TypeError, oversized python int
                    # -> OverflowError, ...), never program problems: fall
                    # back for THIS message only and keep the device program
                    # live for the rest of the stream
                    stats["device_fallbacks"] += 1
                else:
                    try:
                        out, keep = program(dev)
                        return _from_device(out, payload) if bool(keep) \
                            else None
                    except Exception as e:
                        # genuine program failure (impure/untraceable stage,
                        # device error): permanently drop to the host-composed
                        # chain (still zero bus hops)
                        demote(e)
            return host_one(stream, payload)

        def autotune(burst: int, drain_s: float) -> None:
            # occupancy feedback: a burst that fills the current ceiling
            # means the mailbox still had messages left behind; a streak of
            # them means the ceiling — not the arrival rate — is the
            # bottleneck, so double it (pad shapes stay powers of two).
            # Latency feedback runs the other way: a streak of over-budget
            # bursts means the ceiling is past the device's sweet spot and
            # every message is paying the whole burst's wall time — halve it.
            if not tune["auto"]:
                return
            if drain_s > AUTOTUNE_BUDGET_S:
                tune["streak"] = 0  # never grow through a latency breach
                tune["slow"] += 1
                if tune["slow"] >= AUTOTUNE_DOWN_STREAK and tune["cur"] > 1:
                    tune["cur"] = max(1, tune["cur"] // 2)
                    tune["slow"] = 0
                    stats["max_batch_current"] = tune["cur"]
                return
            tune["slow"] = 0
            if burst >= tune["cur"]:
                tune["streak"] += 1
                if tune["streak"] >= AUTOTUNE_STREAK \
                        and tune["cur"] < AUTOTUNE_MAX_BATCH:
                    tune["cur"] = min(tune["cur"] * 2, AUTOTUNE_MAX_BATCH)
                    tune["streak"] = 0
                    stats["max_batch_current"] = tune["cur"]
            else:
                tune["streak"] = 0

        def process_batch(stream: str, payloads: Sequence[dict]) -> list:
            """One vmapped device call for a whole mailbox burst; returns a
            per-message result list (None = filtered), order preserved.
            Pads that divide the mesh run the SPMD-sharded program; bursts
            the device cannot stack (ragged/mixed shapes, non-numeric
            fields) degrade to the per-message path — bit-identical to the
            host chain."""
            if mode["device"] and batched_program is not None \
                    and len(payloads) > 1:
                pad_to = _round_up_pow2(len(payloads))
                t0 = time.monotonic()
                try:
                    dev = _to_device_batched(payloads, pad_to, stats)
                except Exception:
                    # conversion = payload problem (ragged shapes, mixed
                    # dtypes, non-numeric or unconvertible values): burst-
                    # level degrade only — the per-message path below still
                    # tries the device for each message, and counts a
                    # device_fallback only for the ones that truly drop to
                    # the host chain
                    stats["unstackable_bursts"] += 1
                else:
                    sharded = sprog["fn"] if pad_to % ndev == 0 else None
                    try:
                        if sharded is not None:
                            try:
                                out, keep = sharded(dev)
                            except Exception as e:
                                # the single-device batched program stays live
                                retire_sharded(e)
                                sharded = None
                        if sharded is None:
                            out, keep = batched_program(dev)
                        keep = np.asarray(keep)
                    except Exception as e:
                        demote(e)
                    else:
                        stats["batched_bursts"] += 1
                        stats["batched_msgs"] += len(payloads)
                        if sharded is not None:
                            stats["sharded_bursts"] += 1
                        autotune(len(payloads), time.monotonic() - t0)
                        host = _from_device_batched(out, payloads,
                                                    resident=resident)
                        return [host[i] if keep[i] else None
                                for i in range(len(payloads))]
            # per-message fallback: a poison message here must not destroy
            # its already-processed predecessors — hand the successful
            # prefix to the Executor so it is emitted before the crash and
            # only the poison + unprocessed tail count as lost
            results: list = []
            for p in payloads:
                try:
                    results.append(process(stream, p))
                except Exception as e:
                    raise BatchInterrupted(results) from e
            return results

        process.process_batch = process_batch
        process.default_max_batch = max_batch or DEFAULT_MAX_BATCH
        process.stats = stats
        if tune["auto"]:
            # the Executor re-reads this each pump iteration, so a doubled
            # ceiling takes effect on the very next mailbox drain
            process.current_max_batch = lambda: tune["cur"]

        if program is not None and entry_schema is not None:
            zeros = entry_schema.zero_payload()
            if zeros is not None:
                canonical = _round_up_pow2(process.default_max_batch)

                def warmup():
                    # compile before the first real message; the Executor
                    # calls this ahead of the pump loop and keeps the cost
                    # out of the latency EWMA.  The batched program warms at
                    # the canonical (full) burst size — the steady-state
                    # shape under backlog — and the sharded lowering warms
                    # alongside it when the mesh divides that shape.
                    run_device(zeros)
                    if batched_program is not None and canonical > 1:
                        dev = _to_device_batched([zeros, zeros], canonical)
                        batched_program(dev)
                        if sprog["fn"] is not None and canonical % ndev == 0:
                            try:
                                sprog["fn"](dev)
                            except Exception as e:
                                retire_sharded(e)
                process.warmup = warmup
        return process

    return fused_factory


# ---------------------------------------------------------------------------
# The pass
# ---------------------------------------------------------------------------

def _stage_kind(au: AnalyticsUnitSpec) -> str:
    return au.combinator if au.combinator in ("map", "filter") else "au"


def fuse_application(app: Application, *,
                     taps: Iterable[str] = ()) -> Application:
    """Collapse every DEVICE segment of ``app`` into one fused AU + stream.

    Pure: returns a new Application (or ``app`` unchanged when nothing fuses).
    """
    segments = plan_segments(app, taps)
    if not segments:
        return app

    aus = {a.name: a for a in app.analytics_units}
    producer_schema: dict[str, StreamSchema] = {}
    for sensor in app.sensors:
        drv = next((d for d in app.drivers if d.name == sensor.driver), None)
        if drv is not None:
            producer_schema[sensor.name] = drv.output_schema
    for s in app.streams:
        au = aus.get(s.analytics_unit)
        if au is not None:
            producer_schema[s.name] = au.output_schema

    fused_streams: list[StreamSpec] = []
    fused_aus: list[AnalyticsUnitSpec] = []
    folded: set[str] = set()
    au_names = set(aus)
    # exits that feed ANOTHER fused segment's entry keep their arrays
    # device-resident: the linked hop's bus message carries ResidentArray
    # rows the downstream unit re-enters without a host->device transfer
    linked_exits = ({seg[-1].name for seg in segments}
                    & {seg[0].inputs[0] for seg in segments})
    for segment in segments:
        entry, exit_ = segment[0], segment[-1]
        stage_aus = [aus[s.analytics_unit] for s in segment]
        stages = tuple(
            FusedStage(au_name=au.name, stream_name=s.name, factory=au.logic,
                       config=au.config_schema.validate(dict(s.config)),
                       kind=_stage_kind(au), pure_fn=au.pure_fn)
            for s, au in zip(segment, stage_aus))
        name = f"{exit_.name}.fused"
        while name in au_names:
            name += "+"
        au_names.add(name)
        entry_schema = producer_schema.get(entry.inputs[0])
        # batching envelope: the fused unit consumes the ENTRY subject, so a
        # max_batch declared on any folded stage carries over.  When several
        # stages declare one, the stage closest to the segment EXIT wins —
        # the last word in chain order, which is what lets a trailing
        # .scaled(max_batch=1) force per-message dispatch over an earlier
        # stage's burst setting.
        declared_batch = [s.max_batch for s in segment
                          if s.max_batch is not None]
        seg_max_batch = declared_batch[-1] if declared_batch else None
        # the segment's envelope: never exceed ANY stage's declared ceiling;
        # a contradictory pair (one stage's floor above another's ceiling)
        # clamps the floor down rather than violating the ceiling
        hi = max(1, min(au.max_instances for au in stage_aus))
        lo = min(max(au.min_instances for au in stage_aus), hi)
        fused_aus.append(AnalyticsUnitSpec(
            name=name, logic=make_fused_logic(stages, entry_schema,
                                              max_batch=seg_max_batch,
                                              resident=exit_.name
                                              in linked_exits),
            input_schemas=tuple(stage_aus[0].input_schemas),
            output_schema=stage_aus[-1].output_schema,
            placement=Placement.DEVICE,
            min_instances=lo, max_instances=hi,
            fused_stages=tuple(st.au_name for st in stages)))
        # delivery mode follows the ENTRY stream: it governs how instances
        # consume the segment's input subject (interior hops have no bus
        # delivery at all).  Under "group" every fused-unit instance is one
        # member of the exit-named queue group, so a scaled fused segment is
        # a worker pool exactly like a scaled host stream; a keyed entry's
        # key policy is inherited wholesale (each key sticks to one fused
        # instance).  Mid-chain keyed streams never get here — they are
        # segment barriers in plan_segments.
        # durability follows the edges that remain on the bus: the ENTRY's
        # replay_from (the fused unit consumes the entry's input subjects)
        # and the EXIT's durable log (the fused stream publishes under the
        # exit's name).  Interior durable streams never get here — they are
        # segment barriers in plan_segments.
        fused_streams.append(StreamSpec(
            name=exit_.name, analytics_unit=name, inputs=tuple(entry.inputs),
            fixed_instances=1 if any(s.fixed_instances == 1 for s in segment)
            else None,
            delivery=entry.delivery, key=entry.key, steal=entry.steal,
            max_batch=seg_max_batch,
            durable=exit_.durable, retention=exit_.retention,
            replay_from=entry.replay_from))
        folded.update(s.name for s in segment)

    streams = [s for s in app.streams if s.name not in folded] + fused_streams
    referenced = {s.analytics_unit for s in streams}
    units = [a for a in app.analytics_units
             if a.name in referenced or not a.combinator] + fused_aus
    return dataclasses.replace(app, streams=streams, analytics_units=units)
