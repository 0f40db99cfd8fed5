"""From a JAX profiler trace to device busy time, per-op time, exposed
collective time and attributed idle gaps.

The window is the harness's ``chipbench.window`` host span. Device
operations are the events on each TPU plane's ``XLA Ops`` line, named by
their HLO instruction, clipped to the window. Host spans are the events
on the host plane's threads (the harness's ``TraceAnnotation`` spans
among them).

Device and host timestamps in a trace disagree by a millisecond or two.
The device clock is shifted onto the host's by the least shift at which
no program starts on the device before the host finished enqueueing it:
the k-th ``XLA Modules`` event of device 0 against the k-th host
``DoEnqueueProgram`` (the last of each group when every launch enqueues
once per device). Where the counts do not pair up, no shift is applied.

- busy: the union of a device's op intervals; averaged over devices.
- op time: each op's self time (its duration less that of the ops
  nested in it, as a loop's body ops are in the loop op), summed by
  instruction name and averaged over devices.
- collective interval: a collective op's own event, or for an async
  pair the span from its ``-start`` to the ``-done`` that closes it
  (first open start of that kind closes first).
- exposed collective time: the part of the union of collective
  intervals that no other innermost op covers on that device.
- idle gaps: the stretches of the window with no op on device 0, each
  named by the shortest host span that covers its midpoint, preferring
  annotations (harness spans, runtime TraceMe's) to the Python tracer's
  ``$file:line function`` spans.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

WINDOW_SPAN = "chipbench.window"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
ENQUEUE_SPAN = "DoEnqueueProgram"
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|send|recv)(-start|-done)?(\.\d+)?$")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of union(a) that union(b) does not cover."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def _clip(ev: Event, w: Interval) -> Optional[Event]:
    s, e = max(ev[1], w[0]), min(ev[2], w[1])
    return (ev[0], s, e) if e > s else None


def collective_intervals(ops: Sequence[Event]) -> List[Interval]:
    out, open_starts = [], defaultdict(list)
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        m = _COLLECTIVE.match(name)
        if not m:
            continue
        kind, phase = m.group(1), m.group(2)
        if phase == "-start":
            open_starts[kind].append(s)
        elif phase == "-done":
            start = open_starts[kind].pop(0) if open_starts[kind] else s
            out.append((start, e))
        else:
            out.append((s, e))
    return out


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.match(name))


def nesting(ops: Sequence[Event]) -> Tuple[List[float], List[bool]]:
    """Self time of each op and whether it is innermost (holds no other
    op), for ops of one line sorted by start."""
    self_t = [e - s for _, s, e in ops]
    leaf = [True] * len(ops)
    stack: List[int] = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            self_t[stack[-1]] -= e - s
            leaf[stack[-1]] = False
        stack.append(i)
    return self_t, leaf


def reduce_events(device_ops: Dict[str, List[Event]],
                  host_spans: List[Event], window: Interval,
                  top: int = 10,
                  device_async: Optional[Dict[str, List[Event]]] = None
                  ) -> dict:
    """The reduction on plain events (times in ns, device events already
    on the host clock); see the module doc. ``device_async`` holds the
    async ops (in-flight spans) of each device, which count toward
    collective time but not toward busy time."""
    w = window
    device_async = device_async or {}
    window_ns = w[1] - w[0]
    devices = sorted(device_ops)
    busy, exposed, coll = [], [], []
    op_time: Dict[str, float] = defaultdict(float)
    gaps: List[Interval] = []
    for i, dev in enumerate(devices):
        ops = sorted((c for c in (_clip(o, w) for o in device_ops[dev])
                      if c), key=lambda o: (o[1], -o[2]))
        spans = [(s, e) for _, s, e in ops]
        busy.append(measure(spans))
        self_t, leaf = nesting(ops)
        for (name, _, _), t in zip(ops, self_t):
            op_time[name] += t / len(devices)
        inflight = [c for c in (_clip(o, w) for o in
                                device_async.get(dev, [])) if c]
        cint = [c for c in (_clip(("", s, e), w) for s, e in
                            collective_intervals(ops + inflight)) if c]
        cint = [(s, e) for _, s, e in cint]
        compute = [(s, e) for (name, s, e), inner in zip(ops, leaf)
                   if inner and not is_collective(name)]
        coll.append(measure(cint))
        exposed.append(measure(subtract(cint, compute)))
        if i == 0:
            gaps = subtract([w], spans)
    inner = [h for h in host_spans if h[0] != WINDOW_SPAN]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        named.append([_cover((s + e) / 2, inner), (e - s) * 1e-9])
    n = max(len(devices), 1)
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_ns * 1e-9,
        "devices": len(devices),
        "busy_s": sum(busy) / n * 1e-9,
        "collective_s": sum(coll) / n * 1e-9,
        "exposed_comm_s": sum(exposed) / n * 1e-9,
        "n_ops": sum(len(v) for v in device_ops.values()),
        "top_ops": [[k, v * 1e-9] for k, v in ops_sorted],
        "idle_gaps": named,
    }


def _cover(t: float, spans: List[Event]) -> str:
    cover = [h for h in spans if h[1] <= t <= h[2]]
    marked = [h for h in cover if not h[0].startswith("$")] or cover
    return min(marked, key=lambda h: h[2] - h[1])[0] if marked else "(none)"


def _is_device_plane(name: str) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", name) is not None


def op_name(hlo: str) -> str:
    """``%fusion.4 = f32[..] fusion(...)`` -> ``fusion.4``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def clock_shift(modules: List[float], enqueues: List[float]) -> float:
    """Nanoseconds to add to device times; see the module doc."""
    modules, enqueues = sorted(modules), sorted(enqueues)
    if not modules or len(enqueues) % len(modules):
        return 0.0
    k = len(enqueues) // len(modules)
    return max(e - m for m, e in zip(modules, enqueues[k - 1::k]))


def events_from_profile(pd) -> dict:
    """Device ops and async ops per device plane (on the host clock) and
    host spans, from a ``ProfileData``."""
    lines: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            per = lines[plane.name] = {}
            for line in plane.lines:
                per[line.name] = [(op_name(ev.name), ev.start_ns, ev.end_ns)
                                  for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns, ev.end_ns)
                            for ev in line.events)
    first = lines[min(lines)] if lines else {}
    shift = clock_shift([s for _, s, _ in first.get(MODULES_LINE, [])],
                        [e for n, _, e in host if n == ENQUEUE_SPAN])

    def moved(evs):
        return [(n, s + shift, e + shift) for n, s, e in evs]

    return {"ops": {d: moved(v.get(OPS_LINE, [])) for d, v in lines.items()},
            "async": {d: moved(v.get(ASYNC_LINE, []))
                      for d, v in lines.items()},
            "host": host, "shift_ns": shift}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    ev = events_from_profile(ProfileData.from_file(path))
    windows = [h for h in ev["host"] if h[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span")
    w = max(windows, key=lambda h: h[2] - h[1])
    out = reduce_events(ev["ops"], ev["host"], (w[1], w[2]),
                        device_async=ev["async"])
    out["clock_shift_s"] = ev["shift_ns"] * 1e-9
    return out


def reduce_dir(trace_dir: str) -> dict:
    return reduce_file(find_xplane(trace_dir))
