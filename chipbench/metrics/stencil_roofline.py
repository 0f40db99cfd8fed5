"""Least time of one stencil step (the larger of its problem-fixed bytes
over peak HBM bandwidth and its operations over peak; ``chipbench.work``)
over the device's busy time per step in the trace, in percent."""


def read(run):
    t = run.trace
    steps = run.trace_units.get("steps")
    least = run.extras.get("least_step_s")
    if t is None or not steps or not least or t["busy_s"] <= 0:
        return None
    return least / (t["busy_s"] / steps) * 100.0
