"""Mean per stencil step of the profiler's own ``wait-recv`` region in
``HaloProgram.step``: the host's wait for the progress engine to finish
the exchange, after the interior stencil has completed (program span,
host clock)."""


def read(run):
    waits = [e.t_end - e.t_start for e in run.spans if e.name == "wait-recv"]
    steps = run.units.get("steps")
    if not waits or not steps:
        return None
    return sum(waits) / steps * 1e-6
