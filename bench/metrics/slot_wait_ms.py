"""slot_wait_ms: mean time a request waited in the engine between submit
and admission to a KV slot (the engine's slot_wait_s / prefills over the
window)."""
from program_counters import ms_per


def read(run):
    return ms_per(run, ("slot_wait_s",), "prefills")
