"""The benchmark's own arithmetic: peaks, stencil work, model FLOPs."""
import json
import os

import pytest

from chipbench import peaks, work
from chipbench.flops import xlstm as fx

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_v5e_peaks_and_source():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["ici_bits_per_s"] == 1600e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_stencil_work_512():
    # field read + write: 2 * 512^3 * 4 B; six 512x512 faces of 4 B
    assert work.stencil_step_bytes(512, 1, 4) == 2 * 2**29 + 6 * 2**20
    assert work.stencil_step_flops(512) == 8 * 2**27
    t, bound = work.least_time_s(work.stencil_step_flops(512),
                                 work.stencil_step_bytes(512, 1, 4),
                                 peaks.peaks("TPU v5 lite"))
    assert bound == "hbm"
    assert t == pytest.approx((2**30 + 6 * 2**20) / 819e9)


def _xlstm_file():
    with open(os.path.join(BENCH, "configs", "xlstm10-125m.json")) as f:
        return json.load(f)


def test_xlstm_counts_pinned():
    cfg = _xlstm_file()
    assert fx.param_count(cfg) == cfg["params"] == 332_839_872
    assert fx.matmul_param_count(cfg) == 294_009_792
    assert fx.train_flops_per_token(cfg, 2048) == 2_047_174_272.0


def test_xlstm_flops_equal_the_programs():
    from chipbench.drivers.xlstm_train import model_config
    from repro.configs.base import ShapeConfig
    from repro.launch import flops as program_flops
    from repro.models import model as M

    cfg = _xlstm_file()
    prog = model_config(cfg)
    assert fx.param_count(cfg) == M.param_count(prog)
    assert fx.matmul_param_count(cfg) == program_flops.matmul_param_count(prog)
    shape = ShapeConfig(name="train", seq_len=2048, global_batch=16,
                        kind="train")
    per_token = program_flops.model_flops(prog, shape) / (16 * 2048)
    assert fx.train_flops_per_token(cfg, 2048) == pytest.approx(per_token,
                                                                rel=1e-12)
