"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its halo
and collective checks accept right results and reject wrong ones at a
small size on virtual devices. The compile-cache location it relies on."""
import os
import subprocess
import sys
import textwrap

import jax

from repro.core import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_halo_and_collective_checks_pass(subproc, tmp_path):
    out = subproc(textwrap.dedent(f"""
        import jax
        import chip_smoke as cs
        devs = jax.devices()
        for ref in ("host", "device"):
            errs = cs.halo_phase(devs, (2, 2, 1), box=16, reference=ref,
                                 trace_dir={str(tmp_path)!r})
            print(ref, sorted(errs))
        cs.collectives_phase(devs, rows=64)
        print("DONE")
    """), devices=4)
    backends = ("['fused_blocking', 'fused_overlap', 'segmented_explicit', "
                "'segmented_gspmd']")
    assert f"host {backends}" in out
    assert f"device {backends}" in out
    assert "trace: recorded" in out
    assert "DONE" in out


def test_halo_check_catches_a_dropped_face(subproc, tmp_path):
    out = subproc(textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        import chip_smoke as cs
        from repro.comm import halo
        # the exchange delivers zeros in place of each neighbour's face
        halo._shift = lambda x, *a, **k: jnp.zeros_like(x)
        try:
            cs.halo_phase(jax.devices(), (2, 2, 1), box=16,
                          trace_dir={str(tmp_path)!r})
        except cs.SmokeFailure as e:
            print("CAUGHT", e)
    """), devices=4)
    assert "CAUGHT fused_overlap" in out


def test_compile_cache_keeps_a_set_directory(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().splitlines()


def test_compile_cache_keys_on_scopes(subproc, tmp_path):
    """Two versions of a program that differ only in a named scope do not
    share a cache entry (each runs under its own op names); the same
    version compiled again does."""
    out = subproc(textwrap.dedent(f"""
        import os
        os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path)!r}
        import jax, jax.numpy as jnp
        import jax.monitoring as mon
        from repro.core import compile_cache, hlo
        compile_cache.enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        hits = []
        mon.register_event_duration_secs_listener(
            lambda e, d, **kw: hits.append(e) if "cache_retrieval" in e
            else None)

        def version(scope):
            def step(u):
                with jax.named_scope(scope):
                    return jnp.sin(u) * 2.0
            return step

        u = jnp.ones((64, 64))
        for scope in ("halo.old", "halo.new", "halo.new"):
            n = len(hits)
            text = jax.jit(version(scope)).lower(u).compile().as_text()
            names = set(hlo.op_scopes(text).values())
            print(scope, len(hits) - n, any(scope in x for x in names))
    """), devices=1)
    assert out.split("\n")[:3] == ["halo.old 0 True", "halo.new 0 True",
                                   "halo.new 1 True"]
