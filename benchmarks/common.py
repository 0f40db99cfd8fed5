"""Shared helpers for the benchmark harness."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(REPO, "results", "bench")


def run_halo_child(backend: str, devices: int = 8, box: int = 16,
                   steps: int = 2, runs: int = 5, emit_trace: bool = False,
                   emit_hlo_stats: bool = False) -> dict:
    """Run the halo app in a child process on ``devices`` host CPU devices.

    This harness is CPU-only: the child's environment pins JAX to the CPU
    and forces the device count, so it never contends with the parent (or
    anything else) for an attached chip."""
    cmd = [sys.executable, "-m", "benchmarks.halo_child",
           "--backend", backend,
           "--box", str(box), "--steps", str(steps), "--runs", str(runs)]
    if emit_trace:
        cmd.append("--emit-trace")
    if emit_hlo_stats:
        cmd.append("--emit-hlo-stats")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + ":" + REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=REPO, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"halo_child failed: {out.stderr[-2000:]}")
    return json.loads(out.stdout.splitlines()[-1])


def bench_meta() -> dict:
    """Host attribution stamped into every bench result: recorded
    ratios are only comparable across machines when the substrate
    (numpy present/absent + version) and the schedulable core count
    travel with them."""
    try:
        import numpy
        np_version: Optional[str] = numpy.__version__
    except ImportError:
        np_version = None
    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        from repro.corpus.parallel import usable_cores
        cores = usable_cores()
    finally:
        sys.path.pop(0)
    return {
        "python": sys.version.split()[0],
        "numpy": np_version,
        "usable_cores": cores,
    }


def save_json(name: str, payload) -> str:
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, name)
    if isinstance(payload, dict):
        meta = dict(payload.get("meta") or {})
        meta.update(bench_meta())
        payload = dict(payload, meta=meta)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def csv_row(name: str, us_per_call: float, derived: str = "") -> str:
    return f"{name},{us_per_call:.3f},{derived}"
