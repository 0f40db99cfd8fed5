"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --preset smoke \
        --steps 20 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt

Wires every substrate together: config -> mesh -> sharded params ->
profiled train loop -> async checkpoints -> straggler detector -> trace
export. On CPU it runs the reduced presets; on a real TPU fleet the same
driver takes the full configs (the dry-run proves those lower+compile).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.manager import CheckpointManager
from ..checkpoint.straggler import StragglerDetector
from ..configs.archs import get_config
from ..core import regions, timeline
from ..core.collector import global_collector, reset_global_collector
from ..core.compile_cache import enable_compile_cache
from ..core.graphframe import GraphFrame
from ..data.pipeline import DataConfig, SyntheticTokens
from ..models import model as M
from ..optim import adamw
from ..sharding import rules as R
from ..train.step import make_train_step
from .mesh import make_mesh_for


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine",
                    choices=["cosine", "wsd", "constant"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--trace-out", default=None)
    ap.add_argument("--d-model", type=int, default=None,
                    help="override width (e.g. ~100M-param e2e run)")
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, args.preset)
    if args.d_model:
        cfg = dataclasses.replace(
            cfg, d_model=args.d_model,
            d_ff=args.d_model * 4 if cfg.d_ff else 0,
            n_heads=max(4, args.d_model // 64),
            n_kv_heads=max(4, args.d_model // 64), d_head=64)
    if args.layers:
        plen = len(cfg.pattern)
        cfg = dataclasses.replace(
            cfg, n_layers=max(plen, args.layers // plen * plen))
    # MiniCPM trains with WSD per its paper
    schedule = "wsd" if cfg.name.startswith("minicpm") else args.schedule

    mesh = make_mesh_for(len(jax.devices()), args.model_parallel)
    rules = R.make_rules(mesh)
    print(f"arch={cfg.name} preset={args.preset} devices={mesh.devices.size} "
          f"mesh={dict(mesh.shape)}")
    print(f"params: {M.param_count(cfg):,} "
          f"(active {M.active_param_count(cfg):,})")

    opt_cfg = adamw.AdamWConfig(lr=args.lr, schedule=schedule,
                                warmup_steps=max(2, args.steps // 10),
                                total_steps=args.steps)
    data = SyntheticTokens(cfg, DataConfig(batch=args.batch, seq_len=args.seq))

    ckpt = CheckpointManager(args.ckpt_dir, keep=2) if args.ckpt_dir else None
    start_step = 0
    with R.sharding_context(mesh, rules):
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        opt_state = adamw.init_state(params)
        if ckpt and args.resume:
            restored = ckpt.restore()
            if restored:
                start_step, host_state, _ = restored
                from ..checkpoint.elastic import reshard_state
                st = reshard_state(cfg, host_state, mesh)
                params, opt_state = st["params"], st["opt_state"]
                print(f"resumed from step {start_step}")

        step_fn = jax.jit(make_train_step(cfg, opt_cfg),
                          donate_argnums=(0, 1))
        detector = StragglerDetector()
        reset_global_collector()
        losses = []
        for step in range(start_step, args.steps):
            with regions.annotate("train/step", category="app", step=step) :
                with regions.annotate("train/data", category="data"):
                    batch = {k: jnp.asarray(v)
                             for k, v in data.batch_at(step).items()}
                t0 = time.perf_counter()
                with regions.annotate("train/compute", category="api"):
                    params, opt_state, metrics = step_fn(
                        params, opt_state, batch)
                    loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                detector.record(rank=0, step=step, duration_s=dt)
                losses.append(loss)
                if ckpt and (step + 1) % args.ckpt_every == 0:
                    with regions.annotate("train/checkpoint",
                                          category="runtime"):
                        ckpt.save(step + 1, {
                            "params": params, "opt_state": opt_state})
            if step < start_step + 3 or (step + 1) % 10 == 0:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"({dt*1e3:.0f} ms)")
        if ckpt:
            ckpt.save(args.steps, {"params": params, "opt_state": opt_state})
            ckpt.wait()
            ckpt.close()

        events = global_collector().drain()
        gf = GraphFrame.from_events(events)
        print("\nprofile (inclusive seconds):")
        print(gf.tree(metric="sum", fmt="{:.3f}", max_depth=2))
        if args.trace_out:
            timeline.save_trace(timeline.to_chrome_trace(events),
                                args.trace_out)
            print(f"chrome trace -> {args.trace_out}")
        if detector.flagged:
            print("straggler findings:",
                  *[str(f) for f in detector.flagged], sep="\n  ")
        print(f"\nfinal loss {losses[-1]:.4f} (first {losses[0]:.4f})")
        return losses


if __name__ == "__main__":
    main()
