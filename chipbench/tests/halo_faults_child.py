"""Run in a process with four CPU devices: every halo cell once sound and
once with each fault it can have planted under the timed path; prints
one JSON object {case: correct}."""
import json
import sys

import jax

from chipbench.tests.helpers import run, small_cell


def main():
    import repro.comm.halo as halo_mod
    out = {}
    for name in ("halo512-profiled-1chip", "halo512-fused-1chip",
                 "halo512-fused-2x2", "halo512-profiled-2x2"):
        cell = small_cell(name)
        real = cell.driver.build_program
        faults = {
            "sound": lambda tr, mesh, w: real(tr, mesh, w),
            "unchanged": lambda tr, mesh, w: (lambda u: u,
                                              real(tr, mesh, w)[1]),
            "altered": lambda tr, mesh, w: (
                (lambda f: lambda u: f(u).at[3, 3, 3].add(1.0))(
                    real(tr, mesh, w)[0]), real(tr, mesh, w)[1]),
        }
        if cell.chips == 4:
            faults["no_exchange"] = "no_exchange"
        for case, fault in faults.items():
            saved = halo_mod.ppermute
            if fault == "no_exchange":
                halo_mod.ppermute = lambda x, axis, perm, tag=None: x
                cell.driver.build_program = real
            else:
                cell.driver.build_program = fault
            try:
                out[f"{name}/{case}"] = run(cell)["correct"]
            finally:
                cell.driver.build_program = real
                halo_mod.ppermute = saved
    print(json.dumps(out))


if __name__ == "__main__":
    assert len(jax.devices()) == 4, jax.devices()
    sys.exit(main())
