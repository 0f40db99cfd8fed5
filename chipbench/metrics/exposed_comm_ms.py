"""Collective time per stencil step during which no other operation runs
on the device, averaged over devices (device trace)."""


def read(run):
    t = run.trace
    steps = run.trace_units.get("steps")
    if t is None or not steps or t["collective_s"] <= 0:
        return None
    return t["exposed_comm_s"] / steps * 1e3
