"""Cells cut to sizes a CPU test run can hold, and one harness run."""
import time

import jax

from chipbench import bench


# Limits of the training comparison at this size (CPU readings, seeds 12
# and 13): the sound program reads loss 3.1e-4, grad (median leaf)
# 5.6e-4-1.2e-3, change 1.4e-3-2.1e-3; the fp8 control 2.8e-3-4.3e-3,
# 8.7e-3-1.0e-2, 7.5e-3-8.5e-3. Bfloat16 rounding weighs otherwise in a
# model of width 64 and 2 blocks than at the cell's sizes, whose limits
# are in chipbench/configs/xlstm10-125m.json. Both block kinds are in the
# small model, so the sLSTM of the reference is compared with the
# program's too.
SMOKE_LIMITS = {"loss_gap": 1e-3, "grad_gap": 4e-3, "change_gap": 0.04}


# The training cell's files (configuration, traffic, driver, reference)
# stay while the cell is out of BENCHMARK.json, until a control separates
# from the sound program at its size (PERF.md); the tests still run it.
TRAIN_CELL = {"name": "xlstm10-train-b16s2048", "config": "xlstm10-125m",
              "traffic": "xlstm10-train-b16s2048", "chips": 1}


def small_cell(name: str):
    b = bench.load_benchmark()
    if name == TRAIN_CELL["name"]:
        b["workloads"].append(TRAIN_CELL)
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
