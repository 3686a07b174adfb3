"""decode_roofline.code64: the least time the chip could take for the
window's decode steps, each the larger of its FLOPs over 197 TFLOP/s and
its bytes over 819 GB/s (bench/flops_swa_moe.py: attention, router and
head weights once, the weights of the experts the step's tokens hit, the
KV each full and sliding layer reads), as a share of the decode program's
device time in the traced window."""
from readers import DECODE_PROGRAMS
from readers_swa_moe import work


def read(run):
    found = run.program_s(DECODE_PROGRAMS)
    done = work(run)
    if not found or done is None or not done[1]:
        return None
    dims, decode = done[0], done[1]
    least = sum(dims.roofline_s(f, b, run.peaks)[0] for f, b in decode)
    return 100.0 * least / found[1]
