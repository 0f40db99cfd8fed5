"""Bring-up smoke run on the TPU: the quickest proof that the main paths
start on the chip and give right answers.

    python chip_smoke.py              # one chip: halo stencil, trainer, server
    python chip_smoke.py --chips 4    # 2x2 host: sharded halo + collectives

Everything runs in this one process, which holds the chip. Phases, in
order; any failure raises and the script exits non-zero:

1. Device check: the platform must be ``tpu``; there is no CPU fallback.
2. Halo stencil (``repro.comm.halo``) on a (1,1,1) mesh, or (2,2,1) with
   ``--chips 4``, at a 512^3 float32 box per chip, every backend checked
   against a plain periodic 7-point stencil. The segmented explicit
   program runs under the matching fabric's trace recorder, and the
   trace is replayed in-process.
3. (one chip) ``repro.launch.train`` on xlstm-125m at full width: 3 steps,
   finite losses.
4. (one chip) ``repro.launch.serve`` on the same config: 16 decode steps,
   tokens inside the vocabulary.
3'. (``--chips 4``) The comm-layer collectives and ring schedules at 64
   MiB per device against numpy, each result spanning all four devices.

Timings printed here are smoke timings of a single cold run, not metrics.
The last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import functools
import importlib.metadata
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.comm import collectives as C  # noqa: E402
from repro.comm.halo import HaloProgram, make_halo_fn  # noqa: E402
from repro.comm.patterns import ring_perm  # noqa: E402
from repro.comm.progress import ProgressEngine  # noqa: E402
from repro.comm.ring import ring_all_gather, ring_all_reduce  # noqa: E402
from repro.core import analyses  # noqa: E402
from repro.core.compat import make_mesh  # noqa: E402
from repro.core.compile_cache import enable_compile_cache  # noqa: E402
from repro.trace import read_trace, record_collectives, replay  # noqa: E402

BOX = 512            # per-chip edge: a 512 MiB field, 1 MiB faces
HALO_STEPS = 4
# float32 rounding of a 7-term sum taken in another order, plus the face
# correction in comm.halo._apply_halos, which subtracts and re-adds a term
HALO_TOL = 1e-5
COLLECTIVE_ROWS = 4096          # (4096, 4096) float32 = 64 MiB per device
SUM_ULPS = 4                    # 4 addends: at most 3 roundings, plus one
ARCH = "xlstm-125m"
TRACE_DIR = os.path.join(REPO, "results", "chip_smoke")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def device_check(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} jax={jax.__version__} "
          f"jaxlib={importlib.metadata.version('jaxlib')} libtpu={libtpu}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (platform {d.platform!r}); "
                         "this script has no CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX sees {len(devs)}")
    return info


# ---------------------------------------------------------------------------
# halo stencil
# ---------------------------------------------------------------------------

def plain_stencil(u, steps: int, xp=np):
    """Periodic 7-point Laplacian applied ``steps`` times to the whole
    (unsharded) field, in numpy (float64 on the host) or jax.numpy.

    One wrapped copy per step, read through slices: on the TPU, six
    rolls of a 2 GiB field each materialize and overflow the 16 GB."""
    for _ in range(steps):
        p = xp.pad(u, 1, mode="wrap")
        u = (-6.0 * p[1:-1, 1:-1, 1:-1]
             + p[:-2, 1:-1, 1:-1] + p[2:, 1:-1, 1:-1]
             + p[1:-1, :-2, 1:-1] + p[1:-1, 2:, 1:-1]
             + p[1:-1, 1:-1, :-2] + p[1:-1, 1:-1, 2:])
    return u


def check_close(name: str, out, ref, tol: float = HALO_TOL) -> float:
    """max |out - ref| over max |ref|; raises above ``tol``."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if out.shape != ref.shape:
        raise SmokeFailure(f"{name}: shape {out.shape} != {ref.shape}")
    rel = float(np.abs(out - ref).max() / np.abs(ref).max())
    print(f"  {name:24s} max|err|/max|ref| = {rel:.3e} (limit {tol:g})")
    if not rel <= tol:
        raise SmokeFailure(f"{name}: error {rel:.3e} over {tol:g}")
    return rel


def _timed(fn, u):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(u))
    return out, time.perf_counter() - t0


def replay_check(path: str) -> None:
    """Replay a recorded fabric trace in this process and print what it
    holds; the replay must match every recorded op."""
    _, records = read_trace(path)
    recorded = sum(1 for r in records if r["t"] in ("post", "arr"))
    res = replay(path)
    findings = analyses.analyze_all(res.events)
    print(f"  trace: recorded {recorded} ops, replayed {len(res.matches)}, "
          f"divergences {len(res.divergences)}, findings "
          f"{sorted({f.kind for f in findings}) or 'none'}")
    if recorded == 0 or len(res.matches) != recorded or res.divergences:
        raise SmokeFailure(f"trace replay of {path} does not match the "
                           "recorded run")


def halo_phase(devices, dims, box: int = BOX, steps: int = HALO_STEPS,
               seed: int = 0, reference: str = "host",
               trace_dir: str = TRACE_DIR) -> dict:
    """Every halo backend on a ``dims`` mesh of ``devices`` at ``box``^3
    per device, checked against :func:`plain_stencil`.

    ``reference="host"`` runs that stencil in float64 numpy;
    ``"device"`` runs it in float32 on ``devices[0]``, unsharded (for
    fields too large to step in float64 on the host in good time)."""
    mesh = make_mesh(dims, ("x", "y", "z"), devices=devices)
    shape = tuple(d * box for d in dims)
    sharding = NamedSharding(mesh, P("x", "y", "z"))
    u0 = jax.jit(lambda: jax.random.normal(jax.random.key(seed), shape,
                                           jnp.float32),
                 out_shardings=sharding)()
    print(f"halo: mesh {dims}, field {shape} float32 "
          f"({u0.nbytes / 2**20:.0f} MiB), {steps} steps, "
          f"reference on the {reference}")
    t0 = time.perf_counter()
    if reference == "host":
        ref = plain_stencil(np.asarray(u0, np.float64), steps)
    else:
        # one step per call: the whole loop in one program peaks near
        # 14 GiB at a 2 GiB field
        step = jax.jit(functools.partial(plain_stencil, steps=1, xp=jnp))
        ref = jax.device_put(u0, devices[0])
        for _ in range(steps):
            ref = step(ref)
        ref = np.asarray(ref)
    print(f"  reference stencil: {time.perf_counter() - t0:.2f} s")

    errors = {}
    for variant in ("overlap", "blocking"):
        name = f"fused_{variant}"
        t0 = time.perf_counter()
        fn = jax.jit(make_halo_fn(mesh, variant=variant, steps=steps)
                     ).lower(u0).compile()
        compile_s = time.perf_counter() - t0
        fn(u0).block_until_ready()
        out, wall = _timed(fn, u0)
        errors[name] = check_close(name, out, ref)
        print(f"    smoke timing: compile {compile_s:.2f} s, "
              f"{wall / steps * 1e3:.3f} ms/step")
        del out

    os.makedirs(trace_dir, exist_ok=True)
    for explicit in (True, False):
        name = "segmented_" + ("explicit" if explicit else "gspmd")
        prog = HaloProgram(mesh, explicit=explicit)
        engine = ProgressEngine("incoming") if explicit else None
        run = functools.partial(prog.run, steps=steps, engine=engine)
        try:
            if explicit:
                path = os.path.join(trace_dir, "halo_trace.jsonl")
                with record_collectives(path, meta={"program": name}):
                    out, first = _timed(run, u0)
            else:
                out, first = _timed(run, u0)
            errors[name] = check_close(name, out, ref)
            del out
            _, wall = _timed(run, u0)
        finally:
            if engine is not None:
                engine.shutdown()
        print(f"    smoke timing: first run (with compile) {first:.2f} s, "
              f"{wall / steps * 1e3:.3f} ms/step")
        if explicit:
            replay_check(path)
    return errors


# ---------------------------------------------------------------------------
# collectives (four chips)
# ---------------------------------------------------------------------------

def _spans(name: str, out, n: int) -> None:
    devs = out.sharding.device_set
    if len(devs) != n or len(out.addressable_shards) < n:
        raise SmokeFailure(f"{name}: result on {len(devs)} devices, "
                           f"expected {n}")


def _check_sum(name: str, out, ref, abs_sum) -> None:
    err = np.abs(np.asarray(out, np.float64) - ref)
    bound = SUM_ULPS * np.finfo(np.float32).eps * abs_sum
    worst = float((err / np.maximum(bound, np.finfo(np.float32).tiny)).max())
    print(f"  {name:18s} max err / ({SUM_ULPS} ulp bound) = {worst:.3f}")
    if not worst <= 1.0:
        raise SmokeFailure(f"{name}: sum off by more than {SUM_ULPS} ulps")


def _check_equal(name: str, out, ref) -> None:
    same = np.array_equal(np.asarray(out), ref)
    print(f"  {name:18s} exact match: {same}")
    if not same:
        raise SmokeFailure(f"{name}: result differs from numpy")


def collectives_phase(devices, rows: int = COLLECTIVE_ROWS,
                      seed: int = 0) -> None:
    """Each comm-layer collective on a 1-D mesh of ``devices`` with a
    (rows, rows) float32 block per device, against numpy."""
    n = len(devices)
    mesh = make_mesh((n,), ("r",), devices=devices)
    spec = P("r", None)
    x = jax.jit(lambda: jax.random.normal(jax.random.key(seed),
                                          (n * rows, rows), jnp.float32),
                out_shardings=NamedSharding(mesh, spec))()
    print(f"collectives: {n} devices, {x.nbytes // n / 2**20:.0f} MiB "
          "per device")
    hx = np.asarray(x)
    blocks = hx.reshape(n, rows, rows)
    total = blocks.astype(np.float64).sum(0)
    abs_sum = np.abs(blocks.astype(np.float64)).sum(0)
    perm = ring_perm(n, 1)

    def run(name, body, out_spec):
        t0 = time.perf_counter()
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec,
                               out_specs=out_spec))
        out = jax.block_until_ready(fn(x))
        _spans(name, out, n)
        print(f"  {name:18s} smoke timing: {time.perf_counter() - t0:.2f} s "
              "(with compile)")
        return out

    out = run("psum", lambda s: C.psum(s, "r"), P())
    _check_sum("psum", out, total, abs_sum)
    out = run("all_gather", lambda s: C.all_gather(s, "r"), spec)
    _check_equal("all_gather", out, np.concatenate([hx] * n))
    out = run("reduce_scatter", lambda s: C.reduce_scatter(s, "r"), spec)
    _check_sum("reduce_scatter", out, total, abs_sum)
    out = run("all_to_all", lambda s: C.all_to_all(s, "r", 0, 0), spec)
    # device i ends with chunk i of every source, in source order
    chunks = blocks.reshape(n, n, rows // n, rows)
    _check_equal("all_to_all", out,
                 chunks.transpose(1, 0, 2, 3).reshape(n * rows, rows))
    out = run("ppermute", lambda s: C.ppermute(s, "r", perm), spec)
    src = {dst: s for s, dst in perm}
    _check_equal("ppermute", out,
                 np.concatenate([blocks[src[i]] for i in range(n)]))
    out = run("ring_all_gather", lambda s: ring_all_gather(s, "r"), spec)
    _check_equal("ring_all_gather", out, np.concatenate([hx] * n))
    out = run("ring_all_reduce", lambda s: ring_all_reduce(s, "r"), spec)
    _check_sum("ring_all_reduce", out, np.concatenate([total] * n),
               np.concatenate([abs_sum] * n))


# ---------------------------------------------------------------------------
# trainer and server (one chip)
# ---------------------------------------------------------------------------

def train_phase() -> list:
    from repro.launch.train import main as train_main
    losses = train_main(["--arch", ARCH, "--preset", "full", "--batch", "8",
                         "--seq", "2048", "--steps", "3"])
    print(f"train: {ARCH} full width, losses {losses}")
    if len(losses) != 3 or not all(math.isfinite(v) for v in losses):
        raise SmokeFailure(f"train: expected 3 finite losses, got {losses}")
    return losses


def serve_phase() -> None:
    from repro.configs.archs import get_config
    from repro.launch.serve import main as serve_main
    batch, gen = 8, 16
    tokens = np.asarray(serve_main(
        ["--arch", ARCH, "--preset", "full", "--batch", str(batch),
         "--prompt-len", "512", "--gen", str(gen)]))
    vocab = get_config(ARCH).vocab_size
    # one token from the prefill, then one per decode step
    print(f"serve: tokens {tokens.shape}, {gen} decode steps per sequence")
    if tokens.shape != (batch, gen + 1):
        raise SmokeFailure(f"serve: token array {tokens.shape}")
    if tokens.min() < 0 or tokens.max() >= vocab:
        raise SmokeFailure(f"serve: tokens outside [0, {vocab})")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    info = device_check(args.chips)
    print(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()[:args.chips]
    if args.chips == 4:
        halo_phase(devices, (2, 2, 1), seed=args.seed, reference="device")
        collectives_phase(devices, seed=args.seed)
    else:
        halo_phase(devices, (1, 1, 1), seed=args.seed, reference="host")
        train_phase()
        serve_phase()
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
