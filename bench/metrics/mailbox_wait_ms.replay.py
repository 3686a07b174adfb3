"""mailbox_wait_ms.replay: mean time a frame waited between its publish and
the fused unit's pull (the sidecar's mailbox_wait_s / mailbox_msgs over the
window)."""
from program_counters import ms_per


def read(run):
    return ms_per(run, ("mailbox_wait_s",), "mailbox_msgs")
