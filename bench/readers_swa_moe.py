"""Arithmetic shared by the metric readers of cells whose model mixes
sliding-window and full attention with routed experts
(``metrics/<name>.code64.py``): the window's serving work from the
engine's step records and ``flops_swa_moe.py``, and the device time of one
kernel read from the run's own trace.

A program without the step records' ``attended_window`` or expert counters
(one older than them) gives no work, and every reader then reads None."""
from __future__ import annotations

# The grouped-matmul kernel as the compiled program names its custom calls
# (``expert_gmm.<n>``; pinned by tests/test_tpu_compile.py).  The device
# trace names an operation by its instruction, ``%expert_gmm.3 = f32[...]
# custom-call(...)``.
EXPERT_KERNEL = "expert_gmm"


def is_op(event: str, name: str) -> bool:
    """Whether a device-trace operation, ``%expert_gmm.3 = f32[512,896]{...}
    custom-call(...)``, is the instruction ``name`` or ``name.<n>``."""
    inst = event.split(" = ", 1)[0].strip().lstrip("%")
    return inst == name or inst.startswith(name + ".")


def work(run):
    """(dims, decode steps as (flops, bytes) pairs, prefill flops, prompt
    tokens, the expert kernel's least time) of the engine ticks inside the
    window, or None where the records lack what it needs.  The least time
    is taken call by call (a decode step's grouped matmuls are bound by
    their weights, a prefill's by their FLOPs) and summed."""
    from flops_swa_moe import SwaMoeDims
    dims = SwaMoeDims.from_config(run.conf)
    decode, prefill, tokens = [], 0.0, 0
    expert_s = 0.0

    def experts(moe):
        return dims.roofline_s(dims.expert_flops(moe[1]),
                               dims.expert_bytes(moe[1], moe[2]),
                               run.peaks)[0]
    for s in run.steps_in_window():
        if "attended_window" not in s:
            return None
        if s["active"]:
            moe = s.get("decode_moe")
            if moe is None:
                return None
            decode.append((
                dims.decode_flops(s["active"], s["attended_full"],
                                  s["attended_window"]),
                dims.decode_bytes(s["active"], s["attended_full"],
                                  s["attended_window"], moe[2])))
            expert_s += experts(moe)
        for n, moe in zip(s["prefill"], s.get("prefill_moe", [])):
            prefill += dims.prefill_flops(n)
            tokens += n
            expert_s += experts(moe)
    return dims, decode, prefill, tokens, expert_s


def op_device_s(run, prefix: str):
    """Device seconds, in the traced window and summed over devices, of the
    operations whose instruction is ``prefix`` or ``prefix.<n>`` in the
    run's trace (the
    newest under the harness's trace directory); None without a trace or
    such operations.  The reduction the harness keeps lists only the top
    operations, so this reads the trace itself."""
    if run.trace is None:
        return None
    import harness
    import trace_reduce as tr
    from jax.profiler import ProfileData
    try:
        path = tr.find_trace(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    data = ProfileData.from_file(str(path))
    window, spans = None, []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in tr._events(line):
                    if name == tr.WINDOW_SPAN:
                        window = (s, e)
        elif tr._DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == tr._OPS_LINE:
                    spans.extend((s, e) for name, s, e in tr._events(line)
                                 if is_op(name, prefix))
    if window is None or not spans:
        return None
    total = 0.0
    for s, e in spans:
        c = tr._clip(s, e, *window)
        if c is not None:
            total += (c[1] - c[0]) / 1e9
    return total or None
