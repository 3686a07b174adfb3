"""prefill_us_per_token.code64: device time of the prefill programs and
their slot inserts (full cache and window ring) in the traced window over
the real prompt tokens prefilled in the window (not their buckets)."""
from readers import PREFILL_PROGRAMS
from readers_swa_moe import work


def read(run):
    found = run.program_s(PREFILL_PROGRAMS)
    done = work(run)
    if not found or done is None or not done[3]:
        return None
    return found[1] / done[3] * 1e6
