"""The control (the reference in the precision below the configuration's,
put in the program's place) fails the cell's limits, at sizes a CPU run
holds; on the chip ``chipbench/control.py`` reads it at the cells' own
sizes."""
from chipbench.tests.helpers import context, small_cell


def test_halo_control_bf16_fails_the_limit():
    cell = small_cell("halo512-fused-1chip")
    got = cell.driver.control_readings(context(cell, 11))
    limit = cell.config["limits"]["halo_max_err_rel"]
    assert got["control_bf16"]["halo_max_err_rel"] > limit


def test_train_control_and_faults_fail_a_limit():
    cell = small_cell("xlstm10-train-b16s2048")
    got = cell.driver.control_readings(context(cell, 12))
    limits = cell.config["limits"]
    assert all(got["sound"][k] <= lim for k, lim in limits.items()), got
    for variant in ("control_fp8", "fault_half_batch", "fault_altered"):
        assert any(got[variant][k] > lim for k, lim in limits.items()), \
            (variant, got[variant])
