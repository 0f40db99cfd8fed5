# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# stencil7 is the halo program's interior stencil: it is the hot path of
# every chip benchmark cell (the COMB halo step), where XLA's rolled form
# passed over the field about sixteen times per step. Its reference is
# the jnp rolled stencil in comm/halo.py.
