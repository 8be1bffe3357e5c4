"""Measured step time (the window over its steps) over the step time
the plan predicted."""


def read(facts, trace):
    if facts["kind"] != "train" or not facts["steps"]:
        return None
    return facts["window_s"] / facts["steps"] / facts["plan_step_s"]
