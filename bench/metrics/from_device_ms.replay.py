"""from_device_ms.replay: host time per batched burst to copy its results
back and split them into frames (the fused unit's datax.fused.download and
datax.fused.unstack spans: (download_s + unstack_s) / batched_bursts over
the window)."""
from program_counters import ms_per


def read(run):
    return ms_per(run, ("download_s", "unstack_s"), "batched_bursts")
