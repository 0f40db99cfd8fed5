"""Caliper-analog region annotation API (paper §2.2, §4.1, Fig. 6).

    from repro.core import regions

    with regions.annotate("post-send", category="api"):
        ...

Regions nest; the full path is recorded per event, which is what lets the
GraphFrame reconstruct the hierarchical context tree (paper Fig. 1).

While a JAX profiler trace is active (``jax.profiler.start_trace``), every
region is mirrored into it as a ``jax.profiler.TraceAnnotation`` named
``repro.<region name>`` (``repro.wait-recv``, ``repro.BlockingProgress
lock``): a host span on its own thread's line, nested as the region stack
is, on the clock of the trace's device events. The collector's event is
written as without a trace. With no trace active the mirror costs one
``TraceAnnotation.is_enabled()`` check per region and builds nothing.

Categories mirror ExaMPI's runtime-configurable profiling groups (§4.2):
profiling of each category can be switched on/off at runtime to bound
overhead and trace size. The default category set used by the framework:

    app         user/application level phases
    api         public framework entry points (the "MPI procedure calls")
    collective  communication primitives
    runtime     internal machinery (dispatch, queues, checkpoint I/O)
    data        input pipeline
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Any, Iterator, Optional, Set

from jax.profiler import TraceAnnotation

from .collector import Collector, global_collector
from .events import Event

DEFAULT_CATEGORIES = ("app", "api", "collective", "runtime", "data")
TRACE_PREFIX = "repro."          # profiler-trace name of a mirrored region


class ProfilingConfig:
    """Runtime profiling configuration (which categories are live)."""

    def __init__(self, categories: Optional[Set[str]] = None):
        # None => everything enabled
        self.categories: Optional[Set[str]] = categories

    def enabled(self, category: str) -> bool:
        return self.categories is None or category in self.categories


_config = ProfilingConfig()
_tls = threading.local()


_UNSET = object()


def configure(categories=_UNSET) -> None:
    """Runtime re-configuration, like ExaMPI's profiling level toggles.
    ``categories=None`` enables everything; a set enables only those."""
    global _config
    if categories is not _UNSET:
        _config = ProfilingConfig(
            categories=set(categories) if categories is not None else None)


def config() -> ProfilingConfig:
    return _config


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = []
        _tls.stack = st
    return st


def current_path() -> tuple:
    return tuple(name for name, _cat in _stack())


def clock_ns() -> int:
    return time.perf_counter_ns()


@contextlib.contextmanager
def annotate(
    name: str,
    category: str = "app",
    collector: Optional[Collector] = None,
    **attrs: Any,
) -> Iterator[None]:
    """Annotate a region of interest (Caliper's ``cali_begin/end_region``)."""
    if not _config.enabled(category):
        yield
        return
    col = collector or global_collector()
    mirror = (TraceAnnotation(TRACE_PREFIX + name)
              if TraceAnnotation.is_enabled() else None)
    st = _stack()
    st.append((name, category))
    if mirror is not None:
        mirror.__enter__()
    t0 = clock_ns()
    try:
        yield
    finally:
        t1 = clock_ns()
        if mirror is not None:
            mirror.__exit__(None, None, None)
        path = tuple(n for n, _c in st)
        st.pop()
        col.emit(
            Event(
                name=name,
                path=path,
                category=category,
                t_start=t0,
                t_end=t1,
                pid=col.pid,
                tid=col.normalized_tid(),
                attrs=dict(attrs) if attrs else None,
            )
        )


def profiled(name: Optional[str] = None, category: str = "app", **attrs: Any):
    """Decorator form of :func:`annotate`."""

    def deco(fn):
        region_name = name or fn.__name__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with annotate(region_name, category=category, **attrs):
                return fn(*args, **kwargs)

        return wrapper

    return deco
