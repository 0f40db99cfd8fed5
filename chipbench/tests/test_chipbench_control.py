"""The control (the reference in the precision below the configuration's,
put in the program's place) fails the cell's limits, at sizes a CPU run
holds; on the chip ``chipbench/control.py`` reads it at the cells' own
sizes."""
import pytest

from chipbench.tests.helpers import context, small_cell


def test_halo_control_bf16_fails_the_limit():
    cell = small_cell("halo512-fused-1chip")
    got = cell.driver.control_readings(context(cell, 11))
    limit = cell.config["limits"]["halo_max_err_rel"]
    assert got["control_bf16"]["halo_max_err_rel"] > limit


@pytest.fixture(scope="module")
def train_readings():
    cell = small_cell("xlstm10-train-b16s2048")
    return cell.config["limits"], cell.driver.control_readings(
        context(cell, 12))


# variant -> the limit it fails (None: it passes every limit)
TRAIN_VARIANTS = {"sound": None, "control_fp8": "grad_err_head",
                  "fault_half_batch": "grad_err_head",
                  "fault_unchanged": "change_err",
                  "fault_altered": "change_err_head",
                  "fault_sign": "change_err"}


@pytest.mark.parametrize("variant", sorted(TRAIN_VARIANTS))
def test_train_control_and_faults_fail_a_named_limit(variant,
                                                     train_readings):
    limits, got = train_readings
    failed = {k for k, lim in limits.items() if not got[variant][k] <= lim}
    named = TRAIN_VARIANTS[variant]
    assert (named in failed) if named else not failed, (variant,
                                                        got[variant])
