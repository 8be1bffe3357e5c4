"""Share of the traced window in which no operation ran on the device,
in percent, averaged over the chips."""
from chipbench import trace as tr


def read(facts, trace):
    if facts["kind"] != "train" or not trace.devices:
        return None
    lo, hi = trace.window()
    return 100.0 * (1.0 - tr.busy_s(trace) / (hi - lo))
