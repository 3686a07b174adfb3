"""engine_host_ms: host time per engine tick outside its two device syncs
(the engine's datax.engine.tick span less its datax.engine.prefill_wait
and datax.engine.decode_wait spans: (tick_s - device_wait_s) / ticks over
the window)."""
from program_counters import deltas


def read(run):
    d = deltas(run, "ticks", "tick_s", "device_wait_s")
    if d is None or not d[0]:
        return None
    return (d[1] - d[2]) / d[0] * 1e3
