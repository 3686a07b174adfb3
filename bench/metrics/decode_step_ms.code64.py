"""decode_step_ms.code64: device time of the decode program (sliding and
full layers, the routed experts) in the traced window over its calls."""
from readers import DECODE_PROGRAMS


def read(run):
    found = run.program_s(DECODE_PROGRAMS)
    return found[1] / found[0] * 1e3 if found else None
