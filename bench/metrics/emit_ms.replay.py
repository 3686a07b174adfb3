"""emit_ms.replay: host time per mailbox pull to publish the fused unit's
results (the pump's datax.pump.emit span: emit_s / batches over the
window)."""
from program_counters import ms_per


def read(run):
    return ms_per(run, ("emit_s",), "batches")
