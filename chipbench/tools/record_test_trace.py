"""Record the small trace that ``tests/test_trace_reduce.py`` reads, and
print what it holds.

    python chipbench/tools/record_test_trace.py <out_dir>

Run on the chip. Inside a ``chipbench.window`` span it makes three calls
of a small jitted program, each blocked on and followed by a 5 ms host
sleep in a ``test.host_wait`` span, so the device idles in known places.
It then prints every plane, line and event count, and the window's ops
and host spans, from which the test's expected values are worked out.
"""
from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, TraceAnnotation

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import trace_reduce  # noqa: E402
from chipbench.tools.dump_trace import describe  # noqa: E402


def main(out_dir: str) -> None:
    f = jax.jit(lambda x: jnp.sin(x @ x))
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with TraceAnnotation("chipbench.call"):
                f(x).block_until_ready()
            with TraceAnnotation("test.host_wait"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(out_dir)
    print(f"{path}: {os.path.getsize(path)} bytes")
    pd = ProfileData.from_file(path)
    describe(pd)
    device_ops, host = trace_reduce.events_from_profile(pd)
    w = [h for h in host if h[0] == trace_reduce.WINDOW_SPAN][0]
    print("window", w)
    for dev, ops in device_ops.items():
        for op in ops:
            if w[1] <= op[1] <= w[2]:
                print("op", dev, op)
    for h in host:
        if h[0].startswith(("chipbench.", "test.")):
            print("host", h)
    print(trace_reduce.reduce_file(path))


if __name__ == "__main__":
    main(sys.argv[1])
