"""Child process: run the COMB-analog halo app under one comm backend on
the devices JAX sees and emit per-run GraphFrames + wall times + a trace
as JSON.

Invoked by the benchmark harness (``benchmarks.common.run_halo_child``),
which sets the platform and device count in the environment:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m benchmarks.halo_child --backend explicit_overlap \
        --box 32 --steps 4 --runs 5
"""
import argparse
import json


def mesh_dims(n: int) -> tuple:
    """Split n devices over (x, y, z) as evenly as its prime factors allow:
    8 -> (2, 2, 2), 4 -> (2, 2, 1), 6 -> (3, 2, 1)."""
    dims = [1, 1, 1]
    rest, f = n, 2
    factors = []
    while rest > 1:
        while rest % f:
            f += 1
        factors.append(f)
        rest //= f
    for f in sorted(factors, reverse=True):
        dims[dims.index(min(dims))] *= f
    return tuple(sorted(dims, reverse=True))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", required=True)
    ap.add_argument("--box", type=int, default=32, help="local box edge")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--emit-trace", action="store_true")
    ap.add_argument("--emit-hlo-stats", action="store_true")
    args = ap.parse_args()

    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.comm.backends import get_backend
    from repro.comm.halo import HaloProgram, make_halo_fn, make_xla_auto_fn
    from repro.core import regions, timeline
    from repro.core.collector import reset_global_collector
    from repro.core.compat import make_mesh
    from repro.core.compile_cache import enable_compile_cache
    from repro.core.graphframe import GraphFrame

    enable_compile_cache()
    backend = get_backend(args.backend)
    n = len(jax.devices())
    dims = mesh_dims(n)
    mesh = make_mesh(dims, ("x", "y", "z"))
    edge = args.box
    global_shape = (dims[0] * edge, dims[1] * edge, dims[2] * edge)
    sharding = NamedSharding(mesh, P("x", "y", "z"))
    u0 = jax.device_put(
        jnp.asarray(np.random.default_rng(0).standard_normal(global_shape),
                    jnp.float32), sharding)

    from repro.comm.progress import ProgressEngine

    engine = None
    if backend.kind == "auto":
        prog = HaloProgram(mesh, explicit=False)
    else:
        prog = HaloProgram(mesh, explicit=True)
        engine = ProgressEngine(
            "shared" if backend.schedule == "serial" else "incoming")

    def run_once(u):
        return prog.run(u, steps=args.steps, engine=engine,
                        fence_every_op=backend.fence_every_op)

    hlo_stats = None
    if args.emit_hlo_stats:
        from repro.core import hlo as H
        fused = jax.jit(make_halo_fn(mesh, variant=(
            backend.schedule if backend.kind == "explicit" else "overlap"),
            steps=args.steps)) if backend.kind == "explicit" else jax.jit(
            make_xla_auto_fn(mesh, steps=args.steps),
            in_shardings=sharding, out_shardings=sharding)
        txt = fused.lower(u0).compile().as_text()
        st = H.collective_stats(txt)
        hlo_stats = {"count": st.count,
                     "operand_bytes": st.total_operand_bytes,
                     "wire_bytes": st.total_wire_bytes,
                     "by_opcode": {k: dict(v) for k, v in st.by_opcode.items()}}

    out = run_once(u0)                  # warmup/compile
    jax.block_until_ready(out)
    # on the host: without x64, astype(float64) on a device stays float32
    checksum = float(np.abs(np.asarray(out, np.float64)).sum())

    frames, walls, trace = [], [], None
    for r in range(args.runs):
        col = reset_global_collector()
        t0 = time.perf_counter()
        with regions.annotate("add_vars", category="api"):
            u = u0 * 1.0
        out = run_once(u)
        walls.append(time.perf_counter() - t0)
        events = col.drain()
        frames.append(GraphFrame.from_events(events).to_dict())
        if args.emit_trace and r == args.runs - 1:
            trace = timeline.to_chrome_trace(events)

    if engine is not None:
        engine.shutdown()
    print(json.dumps({
        "backend": args.backend,
        "devices": n,
        "frames": frames,
        "walls": walls,
        "checksum": checksum,
        "trace": trace,
        "hlo_stats": hlo_stats,
    }))


if __name__ == "__main__":
    main()
