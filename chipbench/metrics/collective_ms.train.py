"""Device time per window step in collective ops (all-gather,
reduce-scatter, all-reduce, collective-permute, all-to-all, and their
async halves), in ms, on the chip with the most."""
from chipbench import collectives


def read(facts, trace):
    if facts["kind"] != "train" or not facts["steps"] or not trace.devices:
        return None
    return (1e3 * max(c for c, _ in collectives.per_device(trace))
            / facts["steps"])
