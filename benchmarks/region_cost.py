"""Host cost of one ``repro.core.regions.annotate`` region, with no
profiler trace active (the mirror is one ``is_enabled()`` check) and
inside a ``jax.profiler`` trace (the region is mirrored as a
``repro.<name>`` span).

    PYTHONPATH=src python benchmarks/region_cost.py [--n 200000]

Prints one JSON object: microseconds per region in each case, best of
five rounds, and the same for a bare ``TraceAnnotation`` and its
``is_enabled()`` check.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax
from jax.profiler import TraceAnnotation

from repro.core import regions
from repro.core.collector import Collector


def _per_call_us(fn, n: int) -> float:
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        fn(n)
        best = min(best, time.perf_counter() - t0)
    return best / n * 1e6


def _regions(n: int) -> None:
    col = Collector()
    for _ in range(n):
        with regions.annotate("post-comm", category="api", collector=col):
            pass


def _annotations(n: int) -> None:
    for _ in range(n):
        with TraceAnnotation("repro.post-comm"):
            pass


def _checks(n: int) -> None:
    for _ in range(n):
        TraceAnnotation.is_enabled()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200_000)
    args = ap.parse_args(argv)
    out = {"n": args.n, "backend": jax.default_backend(),
           "off": {"annotate_us": _per_call_us(_regions, args.n),
                   "trace_annotation_us": _per_call_us(_annotations, args.n),
                   "is_enabled_us": _per_call_us(_checks, args.n)}}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out["on"] = {
                "annotate_us": _per_call_us(_regions, args.n),
                "trace_annotation_us": _per_call_us(_annotations, args.n)}
        finally:
            jax.profiler.stop_trace()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
