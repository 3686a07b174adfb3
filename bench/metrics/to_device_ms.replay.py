"""to_device_ms.replay: host time per batched burst to stack its frames and
upload them (the fused unit's datax.fused.stack and datax.fused.upload
spans: (stack_s + upload_s) / batched_bursts over the window)."""
from program_counters import ms_per


def read(run):
    return ms_per(run, ("stack_s", "upload_s"), "batched_bursts")
