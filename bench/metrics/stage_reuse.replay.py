"""stage_reuse.replay: share of the fused unit's batched bursts in the
window whose every stacked field went into a host buffer kept from an
earlier burst (100 x stage_reuses / batched_bursts over the window)."""
from program_counters import deltas


def read(run):
    d = deltas(run, "batched_bursts", "stage_reuses")
    if d is None or not d[0]:
        return None
    return 100.0 * d[1] / d[0]
