"""The harness: finds a cell's files by name, times its window, reads its
metrics and decides ``correct``.

Everything that belongs to one configuration, traffic mix, driver or
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

    chipbench/configs/<config>.json     sizes, source, driver, limits
    chipbench/traffic/<cell>.json       the cell's mix
    chipbench/drivers/<driver>.py       build(ctx) -> session
    chipbench/metrics/<metric>.py       read(run) -> float or None

A metric named ``<base>.<variant>`` (one quantity split by the cells
that report it) is read by ``metrics/<base>.py`` unless a file of its
full name exists.

A driver's session has ``call()``, which does one blocking unit of work
and returns how much (``{"steps": 4}``, ``{"tokens": 16384}``), and
``check()``, which runs after the window and returns the numbers it
compared, each as ``(name, value, limit)``; a number passes when it is
finite and at most its limit. ``begin_window()``, ``end_window()`` and
``extras`` (static numbers for metric readers) are optional.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(RuntimeError):
    """The cell cannot be run here (no accelerator, missing file)."""


# ---------------------------------------------------------------------------
# finding a cell's files
# ---------------------------------------------------------------------------

def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json in {root}")
    with open(path) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    if not os.path.exists(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by its path (metric files carry dots in their names)."""
    if not os.path.exists(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with every file it names resolved."""
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: Any
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Callable]


def _applies(metric: dict, cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def resolve(cell_name: str, bench: Optional[dict] = None,
            bench_dir: str = BENCH_DIR) -> Cell:
    bench = bench if bench is not None else load_benchmark(
        os.path.dirname(bench_dir))
    entries = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in entries:
        raise BenchError(f"no workload {cell_name!r}; have {sorted(entries)}")
    w = entries[cell_name]
    config = _load_json(os.path.join(bench_dir, "configs",
                                     w["config"] + ".json"))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    driver_name = traffic.get("driver", config["driver"])
    driver = load_module(os.path.join(bench_dir, "drivers",
                                      driver_name + ".py"), driver_name)
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell_name)]
    layer = [m for m in bench["per_layer"] if _applies(m, cell_name)]
    readers = {}
    for m in e2e + layer:
        path = os.path.join(bench_dir, "metrics", m["name"] + ".py")
        if not os.path.exists(path):
            path = os.path.join(bench_dir, "metrics",
                                m["name"].split(".")[0] + ".py")
        readers[m["name"]] = load_module(path, m["name"]).read
    return Cell(cell_name, w["chips"], config, traffic, driver, e2e, layer,
                readers)


# ---------------------------------------------------------------------------
# what a metric reader sees
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    cell: str
    config: dict
    traffic: dict
    chips: int
    setup_s: float
    window_s: float
    call_s: List[float]                  # wall time of every call
    units: Dict[str, float]              # work summed over the window
    peaks: dict
    extras: Dict[str, Any]
    spans: List[Any] = dataclasses.field(default_factory=list)
    trace: Optional[dict] = None         # trace_reduce.reduce(...) output
    trace_units: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Context:
    """What a driver's ``build`` gets."""
    cell: str
    config: dict
    traffic: dict
    seed: int
    devices: list
    peaks: dict


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def device_check(chips: int, platform: str = "tpu") -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise BenchError(f"no accelerator: JAX platform is "
                         f"{devs[0].platform!r}, this benchmark runs on "
                         f"{platform!r} only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts compilations and persistent-cache loads while armed."""

    def __init__(self):
        import jax.monitoring as mon
        self.count = 0
        self.armed = False

        def on_duration(event, duration, **kw):
            if self.armed and ("backend_compile" in event
                               or "cache_retrieval" in event):
                self.count += 1

        mon.register_event_duration_secs_listener(on_duration)


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def time_window(session, seconds: float, counter: CompileCounter,
                max_calls: Optional[int] = None):
    """Calls ``session.call()`` until ``seconds`` have passed (or
    ``max_calls`` calls). Returns (window_s, per-call seconds, units)."""
    call_s, units = [], {}
    counter.armed = True
    t0 = time.perf_counter()
    deadline = t0 + seconds
    end = t0
    with _annotate("chipbench.window"):
        while True:
            c0 = time.perf_counter()
            if c0 >= deadline or (max_calls is not None
                                  and len(call_s) >= max_calls):
                break
            with _annotate("chipbench.call"):
                done = session.call()
            end = time.perf_counter()
            call_s.append(end - c0)
            for k, v in done.items():
                units[k] = units.get(k, 0) + v
    counter.armed = False
    return end - t0, call_s, units


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def within_limit(value: float, limit: float) -> bool:
    """The pass rule of ``correct``: finite and at most the limit."""
    return isinstance(value, (int, float)) and math.isfinite(value) \
        and value <= limit


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: list, t_start: float, trace_dir: str,
             log=print) -> dict:
    """One run of one cell; returns the result object."""
    import jax
    from chipbench.peaks import peaks

    counter = CompileCounter()
    kind = devices[0].device_kind
    pk = peaks(kind) if devices[0].platform == "tpu" else {}
    ctx = Context(cell.name, cell.config, cell.traffic, seed, devices, pk)
    with _annotate("chipbench.setup"):
        session = cell.driver.build(ctx)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")

    if hasattr(session, "begin_window"):
        session.begin_window()
    window_s, call_s, units = time_window(session, seconds, counter)
    spans = session.end_window() if hasattr(session, "end_window") else []
    compiles = counter.count
    log(f"window {window_s:.3f} s, {len(call_s)} calls, {units}, "
        f"{compiles} compiles in the window")

    summary, trace_units = None, {}
    if trace:
        from chipbench import trace_reduce
        n = int(cell.traffic["trace_calls"])
        jax.profiler.start_trace(trace_dir)
        try:
            t_win, _, trace_units = time_window(session, 1e9, counter,
                                                max_calls=n)
        finally:
            jax.profiler.stop_trace()
        t0 = time.perf_counter()
        summary = trace_reduce.reduce_dir(trace_dir)
        log(f"traced {n} calls in {t_win:.3f} s: busy "
            f"{summary['busy_s']:.6f} s of {summary['window_s']:.6f} s, "
            f"{summary['n_ops']} ops, clock shift "
            f"{summary['clock_shift_s']:.6f} s, read in "
            f"{time.perf_counter() - t0:.1f} s")
    mem = memory_peak_bytes(devices)

    t0 = time.perf_counter()
    with _annotate("chipbench.check"):
        checks = session.check()
    log(f"check {time.perf_counter() - t0:.1f} s")
    correct = bool(checks) and all(within_limit(v, lim)
                                   for _, v, lim in checks)

    run = Run(cell.name, cell.config, cell.traffic, cell.chips, setup_s,
              window_s, call_s, units, pk,
              dict(getattr(session, "extras", {})), spans, summary,
              trace_units)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cell.readers[m["name"]](run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": len(call_s), "failed": 0,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["top_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["compiles_in_window"] = compiles
    result["call_ms"] = call_summary(call_s)
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result


def call_summary(call_s: List[float]) -> dict:
    """Quantiles of the window's per-call wall times, in ms, so that a
    slow run shows whether every call or a few were slow."""
    if not call_s:
        return {}
    ms = sorted(t * 1e3 for t in call_s)
    pick = lambda q: ms[min(len(ms) - 1, int(q * len(ms)))]
    return {"n": len(ms), "min": ms[0], "p10": pick(0.1), "p50": pick(0.5),
            "p90": pick(0.9), "max": ms[-1]}


def print_checks(result: dict, stream=sys.stderr) -> None:
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=stream)
