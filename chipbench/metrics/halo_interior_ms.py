"""Device self time per stencil step of the ops in the program's
``halo.interior`` scope (``stencil_interior``: the rolled 7-point
stencil), averaged over devices, in the traced window (device trace;
``chipbench.scopes``)."""
from chipbench import scopes


def read(run):
    return scopes.scope_ms_per_step(run, "halo.interior")
