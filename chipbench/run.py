"""Run one benchmark cell once on the accelerator this process holds.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (with ``--trace 1``
also ``breakdown``), then the numbers that decided ``correct`` under
``checks``. The same numbers, each beside its limit, are the last lines
on standard error. Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import bench  # noqa: E402

TRACE_DIR = os.path.join(ROOT, "chipbench_out", "trace")


def _log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = bench.resolve(args.workload)
        import jax
        from repro.core.compile_cache import enable_compile_cache
        devices = bench.device_check(cell.chips)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        _log(f"compile cache {enable_compile_cache()}")
    except bench.BenchError as e:
        _log(f"refused: {e}")
        return 2
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    result = bench.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            devices, T_START, TRACE_DIR, log=_log)
    bench.print_checks(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
