"""Window time over every stencil step completed in it (host clock; the
window spans many calls, each blocked on)."""


def read(run):
    steps = run.units.get("steps")
    if not steps:
        return None
    return run.window_s / steps * 1e3
