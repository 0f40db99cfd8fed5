"""Device self time per stencil step of the ops in the program's
``halo.boundary`` scope (``_apply_halos``: the received faces written
over the rolled stencil's wrapped ones), averaged over devices, in the
traced window (device trace; ``chipbench.scopes``)."""
from chipbench import scopes


def read(run):
    return scopes.scope_ms_per_step(run, "halo.boundary")
