"""Record the small trace that ``tests/test_chipbench_scopes.py`` reads
(``data/halo_small.xplane.pb``), and print its splits by scope and by
region.

    python chipbench/tools/record_scope_trace.py <out_dir>

Run on the chip. On a 32^3 box on one chip, inside one
``chipbench.window`` span, each in a ``chipbench.call`` span and blocked
on: two calls of the segmented ``HaloProgram(explicit=True).run(steps=2)``
with an ``incoming`` progress engine (its regions mirrored as
``repro.*`` spans), then one call of the fused overlap program of 2
steps. Every program is compiled before the trace starts. The Python
tracer is off and no HLO proto is kept, so the file stays small.
"""
from __future__ import annotations

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import scopes, trace_reduce  # noqa: E402
from repro.comm.halo import HaloProgram, make_halo_fn  # noqa: E402
from repro.comm.progress import ProgressEngine  # noqa: E402
from repro.core.compat import make_mesh  # noqa: E402

BOX, STEPS = 32, 2


def main(out_dir: str) -> None:
    mesh = make_mesh((1, 1, 1), ("x", "y", "z"), devices=jax.devices()[:1])
    u = jax.device_put(jnp.arange(BOX ** 3, dtype=jnp.float32).reshape(
        (BOX,) * 3) / BOX ** 3, NamedSharding(mesh, P("x", "y", "z")))
    engine = ProgressEngine("incoming")
    segmented = functools.partial(HaloProgram(mesh, explicit=True).run,
                                  steps=STEPS, engine=engine)
    fused = jax.jit(make_halo_fn(mesh, variant="overlap", steps=STEPS))
    calls = [segmented, segmented, fused]
    for fn in (segmented, fused):
        jax.block_until_ready(fn(u))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out_dir, profiler_options=options)
    try:
        with TraceAnnotation(trace_reduce.WINDOW_SPAN):
            for fn in calls:
                with TraceAnnotation("chipbench.call"):
                    jax.block_until_ready(fn(u))
    finally:
        jax.profiler.stop_trace()
        engine.shutdown()
    path = trace_reduce.find_xplane(out_dir)
    print(f"{path}: {os.path.getsize(path)} bytes")
    print("planes", [(p.name, [ln.name for ln in p.lines])
                     for p in ProfileData.from_file(path).planes])
    times = scopes.scope_times(path) or {"by_op": {}}
    for (module, name), (scope, s) in sorted(times["by_op"].items()):
        print("op", module, name, scope, round(s * 1e9))
    print(json.dumps(scopes.report(path, STEPS * len(calls))))
    print(json.dumps(trace_reduce.reduce_file(path)))


if __name__ == "__main__":
    main(sys.argv[1])
