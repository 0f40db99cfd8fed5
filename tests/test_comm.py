"""Communication layer: ring collectives == lax references (8 devices,
subprocess), halo explicit == GSPMD-global, progress-engine semantics."""
import textwrap
import time

import jax
import jax.numpy as jnp

from repro.comm.progress import ProgressEngine
from repro.core import analyses
from repro.core.collector import reset_global_collector


def test_ring_collectives_match_lax(subproc):
    out = subproc(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.compat import make_mesh
        from repro.comm import ring

        mesh = make_mesh((8,), ("r",))
        x = jnp.arange(8 * 16 * 4, dtype=jnp.float32).reshape(8 * 16, 4)

        for schedule in ("serial", "overlap"):
            ag = jax.jit(jax.shard_map(
                lambda s: ring.ring_all_gather(s, "r", schedule=schedule),
                mesh=mesh, in_specs=P("r", None), out_specs=P("r", None)))(x)
            # every shard gathers the full array; out_specs P('r') stacks
            # shard 0's copy first: compare against plain tile
            ref = jax.jit(jax.shard_map(
                lambda s: jax.lax.all_gather(s, "r", axis=0, tiled=True),
                mesh=mesh, in_specs=P("r", None), out_specs=P("r", None)))(x)
            assert jnp.allclose(ag, ref), schedule

            ar = jax.jit(jax.shard_map(
                lambda s: ring.ring_all_reduce(s, "r", schedule=schedule),
                mesh=mesh, in_specs=P("r", None), out_specs=P("r", None)))(x)
            ar_ref = jax.jit(jax.shard_map(
                lambda s: jax.lax.psum(s, "r"),
                mesh=mesh, in_specs=P("r", None), out_specs=P("r", None)))(x)
            assert jnp.allclose(ar, ar_ref, rtol=1e-6), schedule

        # fused all-gather matmul: every shard ends with the full product
        w = jnp.ones((4, 8), jnp.float32) * 0.5
        agm = jax.jit(jax.shard_map(
            lambda s, w: ring.overlap_matmul_allgather(s, w, "r"),
            mesh=mesh, in_specs=(P("r", None), P(None, None)),
            out_specs=P("r", None)))(x, w)
        ref2 = jnp.tile(x @ w, (8, 1))     # stacked per-shard full copies
        assert agm.shape == ref2.shape and jnp.allclose(agm, ref2), \
            "overlap_matmul_allgather"

        # reduce_scatter matmul
        rsm = jax.jit(jax.shard_map(
            lambda s, w: ring.reduce_scatter_matmul(s, w, "r"),
            mesh=mesh, in_specs=(P(None, None), P(None, None)),
            out_specs=P("r", None)))(x[:16], w)
        full = (x[:16] @ w) * 8          # each shard had identical copy
        assert jnp.allclose(rsm, full), "reduce_scatter_matmul"
        print("RING OK")
    """), devices=8)
    assert "RING OK" in out


def test_halo_explicit_matches_gspmd(subproc):
    out = subproc(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.comm.halo import HaloProgram
        from repro.core.compat import make_mesh
        mesh = make_mesh((2, 2, 2), ("x", "y", "z"))
        sh = NamedSharding(mesh, P("x", "y", "z"))
        u = jax.device_put(jnp.asarray(
            np.random.default_rng(0).standard_normal((8, 8, 8)), jnp.float32), sh)
        oe = HaloProgram(mesh, explicit=True).run(u, steps=3)
        oa = HaloProgram(mesh, explicit=False).run(u, steps=3)
        rel = float(jnp.abs(oe - oa).max() / jnp.abs(oa).max())
        assert rel < 1e-5, rel
        print("HALO OK")
    """), devices=8)
    assert "HALO OK" in out


def test_progress_engine_correctness():
    work = jax.jit(lambda x: x * 2)
    x = jnp.arange(8.0)
    for mode in ("shared", "incoming"):
        eng = ProgressEngine(mode)
        reqs = [eng.submit(work, x + i) for i in range(16)]
        for i, r in enumerate(reqs):
            assert jnp.allclose(r.wait(), (x + i) * 2)
        eng.shutdown()


def test_progress_engine_error_propagation():
    def boom(_):
        raise ValueError("boom")

    eng = ProgressEngine("incoming")
    req = eng.submit(boom, 1)
    try:
        req.wait(timeout=10)
        assert False, "expected ValueError"
    except ValueError:
        pass
    finally:
        eng.shutdown()


def test_progress_engine_lifecycle():
    """Deferred start, restart after shutdown, and loud submit errors
    instead of silently-hung requests."""
    import pytest

    eng = ProgressEngine("incoming", process_fn=lambda r: None,
                         autostart=False)
    assert not eng.running
    with pytest.raises(RuntimeError):
        eng.submit(lambda: 1)            # not started yet
    eng.start()
    eng.start()                          # idempotent while running
    assert eng.running
    assert eng.submit(lambda: 41 + 1).wait(timeout=10) == 42
    eng.shutdown()
    eng.shutdown()                       # idempotent when stopped
    assert not eng.running
    with pytest.raises(RuntimeError):
        eng.submit(lambda: 1)            # stopped engines refuse work
    eng.start()                          # restart reuses the engine
    assert eng.submit(lambda: "again").wait(timeout=10) == "again"
    eng.shutdown()


def test_progress_engine_process_fn_and_labels():
    """process_fn replaces the JAX completion hook (pure-python work
    stays JAX-free) and request labels surface in timeout errors."""
    import pytest

    import threading

    done = []
    gate = threading.Event()
    eng = ProgressEngine("incoming", process_fn=done.append)
    try:
        assert eng.submit(lambda: 7, label="seven").wait(timeout=10) == 7
        assert done == [7]
        req = eng.submit(gate.wait, 10, label="stalled-op")
        with pytest.raises(TimeoutError, match="stalled-op"):
            req.wait(timeout=0.05)
    finally:
        gate.set()                       # unblock the worker first
        eng.shutdown()


def test_shared_queue_contends_incoming_does_not():
    """The paper's §4 finding as an assertion: cross-thread lock-region
    contention exists with one queue and vanishes with the second."""
    work = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((512, 512), jnp.float32)
    jax.block_until_ready(work(x))

    def run(mode):
        reset_global_collector()
        eng = ProgressEngine(mode)
        reqs = []
        for i in range(32):
            reqs.append(eng.submit(work, x))
            if i % 4 == 3:
                time.sleep(0.001)
        for r in reqs:
            r.wait()
        eng.shutdown()
        from repro.core.collector import global_collector
        evs = global_collector().drain()
        cont = analyses.contention(evs, name_filter="BlockingProgress")
        isend = [e.duration for e in evs if e.name == "MPI_Isend"]
        return cont, max(isend)

    cont_shared, max_isend_shared = run("shared")
    cont_inc, max_isend_inc = run("incoming")
    assert sum(f.severity for f in cont_shared) > sum(
        f.severity for f in cont_inc)
    assert max_isend_shared > max_isend_inc


def test_backends_registry():
    from repro.comm.backends import BACKENDS, get_backend
    assert set(BACKENDS) >= {"xla_auto", "explicit_serial",
                             "explicit_overlap", "explicit_serial_oversub"}
    assert get_backend("explicit_serial_oversub").fence_every_op
