"""Cells cut to sizes a CPU test run can hold, and one harness run."""
import time

import jax

from chipbench import bench


# Limits of the training comparison at this size, each between the sound
# program's largest reading and the control's or a fault's smallest. CPU
# readings over 2 checked steps, seeds 12 to 14, sound / fp8 control:
# loss_gap 9.0e-5-3.1e-4 / 2.8e-3-4.3e-3; grad_err 0.025-0.052 /
# 0.218-0.229; grad_err_head 0.014-0.017 / 0.139-0.147; change_err
# 0.095-0.129 / 0.398-0.438; change_err_head 0.061-0.071 / 0.293-0.308.
# Half a batch reads 0.69-0.91 on every _err, a state left unchanged 1,
# the update's sign reversed 2 on change_err, the altered head 1122-1138
# on change_err_head. The cell's own limits go into
# chipbench/configs/xlstm10-125m.json once readings on the chip set them.
# Both block kinds are in the small model, so the sLSTM of the reference
# is compared with the program's too.
SMOKE_LIMITS = {"loss_gap": 1e-3, "grad_err": 0.12, "grad_err_head": 0.05,
                "change_err": 0.25, "change_err_head": 0.15}


# Cells whose files are in the tree and which BENCHMARK.json does not list
# yet, for want of chip readings (PERF.md section 7); the tests run them.
DEFERRED = [
    {"name": "xlstm10-train-b16s2048", "config": "xlstm10-125m",
     "traffic": "xlstm10-train-b16s2048", "chips": 1},
    {"name": "halo512-profiled-2x2", "config": "comb-halo-512",
     "traffic": "halo512-profiled-2x2", "chips": 4},
]


def small_cell(name: str):
    b = bench.load_benchmark()
    b["workloads"] += [w for w in DEFERRED if w["name"] == name]
    cell = bench.resolve(name, b)
    if cell.config["driver"] == "halo":
        cell.config = dict(cell.config, box=16)
    else:
        cell.config = dict(cell.config, n_layers=2, d_model=64,
                           vocab_size=256, chunk=16,
                           pattern=["mlstm", "slstm"],
                           proj_factor_slstm=4.0 / 3.0)
        cell.traffic = dict(cell.traffic, batch=2, seq_len=32,
                            reference_rows_per_block=1)
        cell.config["limits"] = SMOKE_LIMITS
    return cell


def run(cell, seed: int = 2**33 + 5, seconds: float = 0.2,
        trace_dir: str = "unused") -> dict:
    """One harness run on the CPU devices, the chip check skipped."""
    return bench.run_cell(cell, seed, seconds, False,
                          jax.devices()[:cell.chips], time.perf_counter(),
                          trace_dir, log=lambda m: None)


def context(cell, seed: int):
    return bench.Context(cell.name, cell.config, cell.traffic, seed,
                         jax.devices()[:cell.chips], {})
