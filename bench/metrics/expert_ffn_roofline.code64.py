"""expert_ffn_roofline.code64: the least time the chip could take for the
grouped matmuls of every routed token-expert pair in the window, decode and
prefill, call by call (the larger of a call's FLOPs over 197 TFLOP/s and
its bytes over 819 GB/s: the weights of the experts hit once, the rows in
and out; bench/flops_swa_moe.py), summed, as a share of the device time of the
``expert_gmm`` kernel's calls in the traced window."""
from readers_swa_moe import EXPERT_KERNEL, op_device_s, work


def read(run):
    done = work(run)
    if done is None or not done[4]:
        return None
    seconds = op_device_s(run, EXPERT_KERNEL)
    if not seconds:
        return None
    return 100.0 * done[4] / seconds
