"""Bytes per chip the compiler laid out for the step (temporaries,
arguments and outputs, less the donated arguments they reuse) over the
plan's predicted peak."""
import math


def read(facts, trace):
    if facts["kind"] != "train" or not math.isfinite(facts["compiled_bytes"]):
        return None
    return facts["compiled_bytes"] / facts["plan_peak_bytes"]
