"""Device-0 idle time per stencil step, in the traced window, that falls
inside the caller thread's ``repro.bench_comm`` spans: the idle that the
segmented, fenced step of ``HaloProgram.step`` causes, apart from the
call loop and ``wait-send`` (program span: the profiler's regions
mirrored into the trace; ``chipbench.scopes``)."""
from chipbench import scopes


def read(run):
    return scopes.region_idle_ms_per_step(run)
