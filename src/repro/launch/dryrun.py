import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax-importing import: jax locks the platform and the
# device count at first init. The dry-run (and only the dry-run) needs 512
# host placeholders; pinning it to the CPU keeps it, and the --all child
# processes that inherit this environment, off any attached chip.

import argparse          # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ..configs.archs import get_config                     # noqa: E402
from ..configs.base import SHAPES, shapes_for              # noqa: E402
from ..core import hlo, hlo_cost                           # noqa: E402
from ..core.device_timeline import (                       # noqa: E402
    extract_schedule, serialization_report)
from ..core.roofline import HW, Roofline                   # noqa: E402
from ..models import model as M                            # noqa: E402
from ..optim import adamw                                  # noqa: E402
from ..sharding import rules as R                          # noqa: E402
from ..train.step import (                                 # noqa: E402
    make_decode_step, make_prefill_step, make_train_step)
from . import flops as F                                   # noqa: E402
from .mesh import make_production_mesh                     # noqa: E402
from .specs import input_specs                             # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def shardings_for(cfg, shape, mesh, rules, specs):
    param_sh = R.tree_shardings(M.param_axes(cfg), mesh, rules,
                                M.param_shapes(cfg))
    if shape.kind == "train":
        opt_sh = {
            "m": param_sh, "v": param_sh,
            "step": NamedSharding(mesh, P()),
        }
        batch_sh = R.batch_shardings(specs["batch"], mesh, rules)
        in_sh = (param_sh, opt_sh, batch_sh)
        out_sh = (param_sh, opt_sh, NamedSharding(mesh, P()))
        return in_sh, out_sh
    if shape.kind == "prefill":
        batch_sh = R.batch_shardings(specs["batch"], mesh, rules)
        cache_sh = R.cache_shardings(
            M.init_cache_shapes(cfg, shape.global_batch, shape.seq_len),
            mesh, rules)
        logits_sh = NamedSharding(mesh, R.pspec(("batch", None, "vocab"), rules))
        return (param_sh, batch_sh), (logits_sh, cache_sh)
    # decode
    cache_sh = R.cache_shardings(specs["caches"], mesh, rules)
    batch_sh = R.batch_shardings(specs["batch"], mesh, rules)
    pos_sh = NamedSharding(mesh, P())
    logits_sh = NamedSharding(mesh, R.pspec(("batch", None, "vocab"), rules))
    tok_sh = NamedSharding(mesh, R.pspec(("batch", None), rules))
    in_sh = (param_sh, cache_sh, batch_sh, pos_sh)
    out_sh = (logits_sh, tok_sh, cache_sh)
    return in_sh, out_sh


def step_and_args(cfg, shape, specs, microbatches: int = 1):
    if shape.kind == "train":
        step = make_train_step(cfg, adamw.AdamWConfig(),
                               microbatches=microbatches)
        args = (specs["params"], specs["opt_state"], specs["batch"])
        donate = (0, 1)
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg)
        args = (specs["params"], specs["batch"])
        donate = ()
    else:
        step = make_decode_step(cfg)
        args = (specs["params"], specs["caches"], specs["batch"],
                specs["pos"])
        donate = (1,)
    return step, args, donate


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save: bool = True, verbose: bool = True,
             microbatches: int = 1, fused_accounting: bool = False,
             tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    rules = R.make_rules(mesh, shape)
    specs = input_specs(cfg, shape)
    in_sh, out_sh = shardings_for(cfg, shape, mesh, rules, specs)
    step, args, donate = step_and_args(cfg, shape, specs,
                                       microbatches=microbatches)

    t0 = time.time()
    with R.sharding_context(mesh, rules):
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    mc = hlo_cost.module_cost(
        txt, vmem_fused_tag="vmem_fused" if fused_accounting else None)
    stats = hlo.collective_stats(txt)            # unscaled (per occurrence)
    model_fl = F.model_flops(cfg, shape)
    roof = Roofline(
        flops=mc.flops,
        hbm_bytes=mc.bytes_accessed,
        wire_bytes=mc.collective_wire_bytes,
        n_chips=n_chips,
        model_flops=model_fl,
    )
    try:
        sched = extract_schedule(txt)
        ser = serialization_report(sched)
        ser_d = {
            "t_compute": ser.t_compute,
            "t_collective_total": ser.t_collective_total,
            "t_collective_exposed": ser.t_collective_exposed,
            "exposed_fraction": ser.exposed_fraction,
            "n_collectives": ser.n_collectives,
            "n_overlapped": ser.n_overlapped,
        }
    except Exception as e:                        # pragma: no cover
        ser_d = {"error": str(e)}

    per_dev_bytes = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                     + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "ok": True,
        "microbatches": microbatches,
        "fused_accounting": fused_accounting,
        "tag": tag,
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_total": per_dev_bytes,
            "fits_hbm": per_dev_bytes <= HW["hbm_gb"] * 1e9,
        },
        "xla_cost_analysis": {
            "flops": ca.get("flops"), "bytes_accessed": ca.get("bytes accessed"),
            "note": "while bodies counted once by XLA; see walker_*",
        },
        "walker": {
            "flops_per_device": mc.flops,
            "bytes_per_device": mc.bytes_accessed,
            "collective_operand_bytes": mc.collective_operand_bytes,
            "collective_wire_bytes": mc.collective_wire_bytes,
            "collective_count": mc.collective_count,
            "collectives_by_opcode": mc.collectives_by_opcode,
            "top_collectives": mc.top_collectives(12),
            "trip_counts": mc.trip_counts[:32],
        },
        "collectives_unscaled": {
            "count": stats.count,
            "operand_bytes": stats.total_operand_bytes,
            "wire_bytes": stats.total_wire_bytes,
            "by_opcode": stats.by_opcode,
        },
        "model_flops": model_fl,
        "roofline": roof.to_dict(),
        "schedule": ser_d,
    }
    if verbose:
        print(f"== {arch} x {shape_name} on {result['mesh']} "
              f"({n_chips} chips) ==")
        print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory/device: {per_dev_bytes/1e9:.2f} GB "
              f"(fits 16GB: {result['memory']['fits_hbm']})")
        print(f"  {compiled.memory_analysis()}")
        print(f"  cost_analysis flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e}")
        print(f"  walker flops/dev={mc.flops:.3e} bytes/dev="
              f"{mc.bytes_accessed:.3e} wire/dev="
              f"{mc.collective_wire_bytes:.3e}")
        print("  roofline: " + roof.summary())
        print(f"  {json.dumps(ser_d)}")
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fname = f"{arch}__{shape_name}__{result['mesh']}{suffix}.json"
        with open(os.path.join(RESULTS_DIR, fname), "w") as f:
            json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every cell in subprocesses (fault-isolated)")
    ap.add_argument("--no-save", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--fused-accounting", action="store_true",
                    help="charge vmem_fused-tagged kernel interiors zero "
                         "HBM bytes (the Pallas-kernel-equivalent path)")
    ap.add_argument("--tag", default="",
                    help="suffix for the result JSON (e.g. 'opt')")
    args = ap.parse_args()

    if args.all:
        import subprocess
        from ..configs.archs import ARCHS

        failures = []
        for arch in ARCHS:
            cfg = get_config(arch)
            for shape_name in shapes_for(cfg):
                for mp in (False, True):
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape_name]
                    if mp:
                        cmd.append("--multi-pod")
                    print(">>", " ".join(cmd), flush=True)
                    rc = subprocess.call(cmd)
                    if rc != 0:
                        failures.append((arch, shape_name, mp))
        print(f"dryrun --all finished; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    try:
        run_cell(args.arch, args.shape, args.multi_pod,
                 save=not args.no_save, microbatches=args.microbatches,
                 fused_accounting=args.fused_accounting, tag=args.tag)
    except Exception:
        traceback.print_exc()
        # record the failure for the driver
        os.makedirs(RESULTS_DIR, exist_ok=True)
        mesh_name = "2x16x16" if args.multi_pod else "16x16"
        fname = f"{args.arch}__{args.shape}__{mesh_name}.json"
        with open(os.path.join(RESULTS_DIR, fname), "w") as f:
            json.dump({"arch": args.arch, "shape": args.shape,
                       "mesh": mesh_name, "ok": False,
                       "error": traceback.format_exc()[-2000:]}, f)
        sys.exit(1)


if __name__ == "__main__":
    main()
