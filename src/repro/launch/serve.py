"""Batched serving driver: prefill + greedy decode with profiling.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --preset smoke \
        --batch 4 --prompt-len 32 --gen 16

Serves a batch of synthetic prompts through the real prefill/decode steps
(same code the dry-run lowers at 512 chips), with per-phase profiling
regions and a tokens/s report.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..configs.archs import get_config
from ..core import regions
from ..core.collector import global_collector, reset_global_collector
from ..core.compile_cache import enable_compile_cache
from ..core.graphframe import GraphFrame
from ..models import model as M
from ..train.step import make_decode_step, make_prefill_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--preset", default="smoke")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--telemetry", action="store_true",
                    help="serve live counter/region telemetry over "
                         "HTTP/SSE while prefill/decode run")
    ap.add_argument("--telemetry-port", type=int, default=0,
                    help="bind port for --telemetry (default: ephemeral)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, args.preset)
    if cfg.input_mode != "tokens":
        raise SystemExit(f"{cfg.name}: serving demo expects token input")
    B, P, G = args.batch, args.prompt_len, args.gen
    total = P + G
    params = M.init_params(jax.random.PRNGKey(0), cfg, dtype=cfg.dtype)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                                 cfg.vocab_size)

    prefill = jax.jit(make_prefill_step(cfg))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))

    collector = reset_global_collector()
    bridge = server = None
    if args.telemetry:
        from ..core.counters import global_registry
        from ..telemetry import TelemetryBridge, TelemetryServer
        bridge = TelemetryBridge(session=f"serve[{cfg.name}]")
        bridge.watch(global_registry(), name="counters")
        bridge.watch_events(collector, name="regions")
        server = TelemetryServer(bridge, port=args.telemetry_port).start()
        bridge.start()
        print(f"telemetry: {server.url}/metrics | /stream | /findings")

    with regions.annotate("serve/prefill", category="api"):
        logits, caches = prefill(params, {"tokens": prompts})
        jax.block_until_ready(logits)
    # grow caches to generation capacity
    def grow(path, arr):
        nm = path[-1].key
        if nm in ("k", "v") and arr.ndim == 5 and arr.shape[2] == P:
            pad = jnp.zeros((arr.shape[0], arr.shape[1], total - P)
                            + arr.shape[3:], arr.dtype)
            return jnp.concatenate([arr, pad], axis=2)
        if nm == "pos" and arr.ndim == 2 and arr.shape[1] == P:
            return jnp.concatenate(
                [arr, jnp.full((arr.shape[0], total - P), -1, jnp.int32)], 1)
        return arr

    caches = jax.tree_util.tree_map_with_path(grow, caches)
    token = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)[:, None]
    out_tokens = [token]
    t0 = time.perf_counter()
    for t in range(P, total):
        with regions.annotate("serve/decode_step", category="api", pos=t):
            logits, next_tok, caches = decode(
                params, caches, {"tokens": token}, jnp.int32(t))
            token = next_tok[:, 0][:, None]
            out_tokens.append(token)
    jax.block_until_ready(token)
    dt = time.perf_counter() - t0
    gen = jnp.concatenate(out_tokens, axis=1)
    print(f"{cfg.name}: prefill {B}x{P}, generated {B}x{G} greedy tokens")
    print(f"decode throughput: {B * G / dt:.1f} tok/s "
          f"({dt / G * 1e3:.1f} ms/step)")
    print("sample:", gen[0, :16].tolist())
    if bridge is not None:
        bridge.stop()
        print(f"telemetry: {bridge.polls} polls, {bridge.deltas_total} "
              f"deltas, {len(bridge.findings_json())} live findings")
        server.stop()
        bridge.close()
    gf = GraphFrame.from_events(global_collector().drain())
    print(gf.tree(metric="sum", fmt="{:.3f}", max_depth=1))
    return gen


if __name__ == "__main__":
    main()
