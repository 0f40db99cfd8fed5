"""``correct`` comes out false when the timed path is broken underneath:
each fault a cell can have, planted with the chip check skipped, at
sizes a CPU run holds. The sound runs of the same cells come out true,
which also compares the program with the plain references."""
import json
import os
import subprocess
import sys

import jax
import pytest

from chipbench.tests.helpers import run, small_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_halo_cells_catch_each_fault():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.tests.halo_faults_child"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    for case, correct in got.items():
        assert correct is case.endswith("/sound"), (case, got)
    assert {"halo512-fused-2x2/no_exchange",
            "halo512-profiled-2x2/no_exchange"} <= set(got)


def _broken_step(kind):
    import repro.train.step as step_mod

    def make(cfg, opt_cfg):
        real = step_mod.make_train_step(cfg, opt_cfg)

        def step(params, state, batch):
            if kind == "half_batch":
                n = batch["tokens"].shape[0] // 2
                return real(params, state,
                            jax.tree.map(lambda x: x[:n], batch))
            new_p, new_s, metrics = real(params, state, batch)
            if kind == "unchanged":
                return params, state, metrics
            if kind == "sign":                    # the update reversed
                return jax.tree.map(lambda p, q: 2 * p - q, params,
                                    new_p), new_s, metrics
            lm = new_p["lm_head"].at[0, 0].add(1.0)            # altered
            return dict(new_p, lm_head=lm), new_s, metrics
        return step
    return make


# fault -> a limit it fails (None: the sound program, which fails none)
TRAIN_FAULTS = {None: None, "unchanged": "change_err",
                "half_batch": "grad_err_head", "altered": "change_err_head",
                "sign": "change_err"}


@pytest.mark.parametrize("fault", list(TRAIN_FAULTS))
def test_train_cell_catches_each_fault(fault, monkeypatch):
    cell = small_cell("xlstm10-train-b16s2048")
    if fault:
        monkeypatch.setattr(cell.driver, "make_train_step",
                            _broken_step(fault))
    result = run(cell)
    failed = {k for k, c in result["checks"].items()
              if not c["value"] <= c["limit"]}
    assert result["correct"] is (fault is None), result["checks"]
    if fault:
        assert TRAIN_FAULTS[fault] in failed, result["checks"]


def test_train_cell_refuses_a_config_without_its_limits():
    cell = small_cell("xlstm10-train-b16s2048")
    cell.config = dict(cell.config, limits={"grad_err": 0.12})
    with pytest.raises(ValueError, match="limits"):
        run(cell)
