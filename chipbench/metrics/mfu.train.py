"""Model FLOPs of the window's steps over the window's time, the chips
and their bf16 peak, in percent (flops/dense.py counts them)."""


def read(facts, trace):
    if facts["kind"] != "train" or not facts["steps"]:
        return None
    return (100.0 * facts["flops_per_step"] * facts["steps"]
            / (facts["window_s"] * facts["chips"] * facts["peak_flops"]))
