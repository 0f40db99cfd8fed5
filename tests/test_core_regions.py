"""Region annotation + collector: nesting, categories, thread safety."""
import threading
import time

from repro.core import annotate, configure, regions
from repro.core.collector import Collector, reset_global_collector


def setup_function(_fn):
    configure(categories=None)
    reset_global_collector()


def test_nesting_paths():
    col = reset_global_collector()
    with annotate("a"):
        with annotate("b"):
            with annotate("c", category="api"):
                pass
        with annotate("d"):
            pass
    evs = col.drain()
    paths = sorted(e.key for e in evs)
    assert paths == ["a", "a/b", "a/b/c", "a/d"]
    inner = [e for e in evs if e.name == "c"][0]
    outer = [e for e in evs if e.name == "a"][0]
    assert inner.t_start >= outer.t_start
    assert inner.t_end <= outer.t_end
    assert inner.category == "api"


def test_category_toggle_runtime():
    col = reset_global_collector()
    configure(categories={"api"})
    with annotate("app_region", category="app"):
        with annotate("api_region", category="api"):
            pass
    configure(categories=None)
    evs = col.drain()
    names = [e.name for e in evs]
    assert "api_region" in names and "app_region" not in names
    # disabled parents do not appear in child paths
    assert [e for e in evs if e.name == "api_region"][0].path == ("api_region",)


def test_decorator():
    col = reset_global_collector()

    @regions.profiled(category="runtime")
    def work():
        return 41 + 1

    assert work() == 42
    evs = col.drain()
    assert evs[0].name == "work" and evs[0].category == "runtime"


def test_thread_safety_and_tids():
    col = reset_global_collector()
    n_threads, n_events = 8, 200
    barrier = threading.Barrier(n_threads)

    def worker(i):
        barrier.wait()
        for k in range(n_events):
            with annotate(f"t{i}", category="app"):
                pass

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = col.drain()
    assert len(evs) == n_threads * n_events
    tids = {e.tid for e in evs}
    assert len(tids) == n_threads


def test_durations_are_positive_and_ordered():
    col = reset_global_collector()
    with annotate("outer"):
        time.sleep(0.01)
    ev = col.drain()[0]
    assert ev.duration >= 10_000_000  # >= 10ms in ns


def _repro_lines(trace_dir):
    """The ``repro.*`` host spans of a recorded trace, one list per
    thread line that holds any."""
    import glob
    import os

    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                     recursive=True)[0]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.end_ns) for e in line.events
                   if e.name.startswith(regions.TRACE_PREFIX)]
            if evs:
                lines.append(evs)
    return lines


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_regions_mirrored_into_profiler_trace(tmp_path):
    """Under a jax.profiler trace every region is also a repro.* host
    span, on its own thread's line, nested as the region stack is; the
    collector's events are the same as without a trace."""
    import jax

    from repro.comm.progress import LOCK_REGION, ProgressEngine

    col = reset_global_collector()
    engine = ProgressEngine("incoming", process_fn=lambda result: None)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with annotate("outer"):
            with annotate("inner", category="api"):
                engine.submit(lambda: 7).wait(timeout=10)
    finally:
        jax.profiler.stop_trace()
        engine.shutdown()
    assert {e.key for e in col.drain()} >= {"outer", "outer/inner"}

    lines = _repro_lines(tmp_path)
    caller = [ln for ln in lines
              if any(n == "repro.outer" for n, _, _ in ln)]
    assert len(caller) == 1
    spans = {n: (n, s, e) for n, s, e in caller[0]}
    assert _inside(spans["repro.inner"], spans["repro.outer"])
    for name in ("repro.MPI_Isend", "repro.MPI_Wait"):
        assert _inside(spans[name], spans["repro.inner"])
    assert _inside(spans["repro." + LOCK_REGION], spans["repro.MPI_Isend"])
    progress = [ln for ln in lines if ln is not caller[0]
                and any(n == "repro.progress/process" for n, _, _ in ln)]
    assert len(progress) == 1
    assert "repro." + LOCK_REGION in {n for n, _, _ in progress[0]}
    assert not {n for n, _, _ in progress[0]} & {"repro.outer",
                                                  "repro.inner"}


class _CountingAnnotation:
    enabled = False
    built = []

    def __init__(self, name):
        self.name = name
        self.built.append(name)

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.built.append("enter " + self.name)

    def __exit__(self, *exc):
        self.built.append("exit " + self.name)


def test_no_trace_annotation_without_an_active_trace(monkeypatch):
    monkeypatch.setattr(regions, "TraceAnnotation", _CountingAnnotation)
    monkeypatch.setattr(_CountingAnnotation, "built", [])
    col = reset_global_collector()
    with annotate("a"):
        with annotate("b"):
            pass
    assert _CountingAnnotation.built == []
    assert len(col.drain()) == 2

    monkeypatch.setattr(_CountingAnnotation, "enabled", True)
    with annotate("c", category="api"):
        pass
    configure(categories={"api"})
    with annotate("skipped", category="app"):
        pass
    assert _CountingAnnotation.built == ["repro.c", "enter repro.c",
                                         "exit repro.c"]
    assert [e.name for e in col.drain()] == ["a", "b", "c"]
