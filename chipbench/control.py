"""Read a cell's control, and the faults planted in the reference, on
several seeds in one process.

    python chipbench/control.py --workload <name> --seeds <n> [<n> ...]

Prints one JSON line per seed: for each variant (the control computed
in the precision below the configuration's, for training cells the
faults planted in the reference put in the program's place, and the
program's own ``sound`` readings) the numbers that ``correct`` compares
or reads beside them, against the plain reference, and ``caught``:
whether the pass rule of ``correct`` fails the variant on one of the
configuration's limits. Exits 1 if the sound program is caught or a
control or fault is not, on some seed. A configuration with no limits
yet catches nothing, so the run exits 1 and its readings are what the
limits are then set from (PERF.md). The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = bench.resolve(args.workload)
    import jax
    from chipbench.peaks import peaks
    from repro.core.compile_cache import enable_compile_cache
    devices = bench.device_check(cell.chips)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    limits = cell.config.get("limits", {})
    wrong = 0
    for seed in args.seeds:
        ctx = bench.Context(cell.name, cell.config, cell.traffic, seed,
                            devices, peaks(devices[0].device_kind))
        line, t0 = {"seed": seed}, time.perf_counter()
        for variant, got in cell.driver.control_readings(ctx).items():
            caught = not all(bench.within_limit(v, limits[k])
                             for k, v in got.items() if k in limits)
            wrong += caught if variant == "sound" else not caught
            line[variant] = dict(got, caught=caught)
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
