"""expert_load_max.code64: how far the busiest expert runs above the mean,
over the window: the engine's moe_expert_tokens_max (the busiest expert's
routed pairs, per layer per call, summed) over its moe_routed_pairs divided
by the number of experts.  1 is a perfect balance; a grouped matmul's time
follows the busiest expert."""
from program_counters import deltas


def read(run):
    d = deltas(run, "moe_expert_tokens_max", "moe_routed_pairs")
    if d is None or not d[1]:
        return None
    return d[0] / (d[1] / run.conf["num_experts"])
