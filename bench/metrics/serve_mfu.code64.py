"""serve_mfu.code64: the whole serving step's share of the chip's peak:
FLOPs of the decode steps and prefills the engine ran in the window
(bench/flops_swa_moe.py: real tokens, active experts only, windowed
attention pairs) over the window's seconds times 197 TFLOP/s."""
from readers_swa_moe import work


def read(run):
    done = work(run)
    if done is None:
        return None
    flops = sum(f for f, _ in done[1]) + done[2]
    if not flops:
        return None
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops"]
                            * run.chips)
