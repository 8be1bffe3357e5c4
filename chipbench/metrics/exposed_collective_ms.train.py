"""Device time per window step in which a collective runs and no other
op does, in ms, on the chip with the most."""
from chipbench import collectives


def read(facts, trace):
    if facts["kind"] != "train" or not facts["steps"] or not trace.devices:
        return None
    return (1e3 * max(x for _, x in collectives.per_device(trace))
            / facts["steps"])
