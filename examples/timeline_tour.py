"""Timeline profiling (paper method 2), end to end.

    PYTHONPATH=src:. python examples/timeline_tour.py

1. Runs the halo app with the one-queue progress engine and captures a
   two-thread trace (user thread + progress thread).
2. Runs the automated timeline analyses of §4.1 — the contention detector
   finds the BlockingProgress-lock overlap exactly like the paper's Fig 8.
3. Re-runs with the second (incoming) queue and shows the contention gone
   (Fig 9), plus the Isend-latency-vs-load curves (Fig 10).
4. Also derives the *modeled device timeline* from compiled HLO — the TPU
   adaptation where collective exposure is read from the schedule itself.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import time

import jax
import jax.numpy as jnp

from repro.comm.progress import ProgressEngine
from repro.core import analyses, timeline
from repro.core.collector import global_collector, reset_global_collector


def run_engine(mode: str, n_requests: int = 48):
    work = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.float32)
    jax.block_until_ready(work(x))
    reset_global_collector()
    eng = ProgressEngine(mode)
    reqs = []
    # staggered submission so the user thread keeps enqueueing while the
    # progress thread is mid-processing — the realistic steady state
    for i in range(n_requests):
        reqs.append(eng.submit(work, x))
        if i % 4 == 3:
            time.sleep(0.002)
    for r in reqs:
        r.wait()
    eng.shutdown()
    return global_collector().drain()


def main():
    print("== one shared queue (pre-fix ExaMPI) ==")
    ev_old = run_engine("shared")
    findings = analyses.contention(ev_old, name_filter="BlockingProgress")
    print(analyses.report(findings, limit=5))
    isend_old = [e.duration / 1e3 for e in ev_old if e.name == "MPI_Isend"]
    print(f"MPI_Isend mean {sum(isend_old)/len(isend_old):.1f} us "
          f"max {max(isend_old):.1f} us over {len(isend_old)} calls")
    timeline.save_trace(timeline.to_chrome_trace(
        ev_old, thread_names={0: "user thread", 1: "progress thread"}),
        "/tmp/timeline_shared_queue.json")

    print("\n== second incoming queue (the fix) ==")
    ev_new = run_engine("incoming")
    findings_new = analyses.contention(ev_new, name_filter="BlockingProgress")
    print(analyses.report(findings_new, limit=5))
    isend_new = [e.duration / 1e3 for e in ev_new if e.name == "MPI_Isend"]
    print(f"MPI_Isend mean {sum(isend_new)/len(isend_new):.1f} us "
          f"max {max(isend_new):.1f} us")
    timeline.save_trace(timeline.to_chrome_trace(
        ev_new, thread_names={0: "user thread", 1: "progress thread"}),
        "/tmp/timeline_incoming_queue.json")

    print("\ntraces: /tmp/timeline_shared_queue.json, "
          "/tmp/timeline_incoming_queue.json (chrome://tracing)")

    print("\n== modeled device timeline from compiled HLO (TPU adaptation) ==")
    from repro.core import device_timeline as DT
    from jax.sharding import PartitionSpec as P
    from repro.core.compat import make_mesh
    mesh = make_mesh((1,), ("model",))

    def tp_layer(x, w):
        y = jnp.einsum("bd,df->bf", x, w)
        return jax.lax.psum(y, "model")

    f = jax.shard_map(tp_layer, mesh=mesh,
                  in_specs=(P(None, None), P(None, "model")),
                  out_specs=P(None, None))
    txt = jax.jit(f).lower(
        jax.ShapeDtypeStruct((128, 256), jnp.bfloat16),
        jax.ShapeDtypeStruct((256, 512), jnp.bfloat16)).compile().as_text()
    segs = DT.extract_schedule(txt)
    rep = DT.serialization_report(segs)
    print(rep.summary())


if __name__ == "__main__":
    main()
