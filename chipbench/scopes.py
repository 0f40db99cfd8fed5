"""Device time by program scope, and device idle by program region, from
the profiler trace that a ``--trace 1`` run leaves in ``chipbench_out/trace``.

Scopes. The halo program names its work with ``jax.named_scope``
(:data:`SCOPES`). XLA keeps the scope path in each HLO instruction's
``op_name`` metadata (a fusion takes its root op's), and the TPU runtime
copies it into the trace as the ``tf_op`` stat of each op's event
metadata, beside the ``program_id`` of the op's module.
``jax.profiler.ProfileData`` does not expose event-metadata stats, so
:func:`op_tags` reads them from the ``.xplane.pb`` protobuf itself. An op
event is keyed by (module, instruction): the module is the enclosing
event on the device's ``XLA Modules`` line, whose name ends in
``(<program_id>)``; the instruction is the op's HLO name (``fusion.4``).
Its scope is the first component of its ``tf_op`` path that is in
:data:`SCOPES`; an op with none is unscoped (one XLA added, such as a
cross-program prefetch ``copy-start``, or one of a program that names no
scope). Self time is as in ``trace_reduce``: an op's duration less that
of the ops nested in it, clipped to the window.

Regions. ``repro.core.regions`` mirrors each host region into the trace as
a ``repro.<name>`` span on its thread's line. The caller thread is the
line that holds the harness's ``chipbench.window`` span. Device-0 idle
(the window less the union of its ops) is split by the caller's innermost
``repro.*`` span, and the part inside ``repro.bench_comm`` (one stencil
step of ``HaloProgram.step``) is reported on its own.

Device events come through ``trace_reduce.events_from_profile``, so they
sit on the host clock by the same shift, and the window is the same
``chipbench.window`` span, as every other device-trace metric.

    python chipbench/scopes.py [trace_dir] --steps N

prints both splits per step, in ms, as one JSON object.
"""
from __future__ import annotations

import argparse
import bisect
import functools
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import trace_reduce as tr  # noqa: E402

SCOPES = ("halo.extract", "halo.exchange", "halo.interior", "halo.boundary")
UNSCOPED = "(unscoped)"
REGION_PREFIX = "repro."
BENCH_COMM = REGION_PREFIX + "bench_comm"
OUTSIDE = "(no region)"
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench_out", "trace")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def scope_of(tf_op: Optional[str]) -> Optional[str]:
    """``jit(local)/halo.interior/add:add`` -> ``halo.interior``."""
    for part in (tf_op or "").split("/"):
        part = part.split(":", 1)[0]
        if part in SCOPES:
            return part
    return None


# ---------------------------------------------------------------------------
# event-metadata stats, from the xplane protobuf (tsl/profiler xplane.proto:
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
# .stat_metadata = 5, maps of key 1 to value 2; XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1, values 2-7)
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint or fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _map_entries(entries):
    for entry in entries:
        kv = dict(_fields(entry))
        yield kv.get(1, 0), kv.get(2, b"")


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, object]:
    name, value = "", None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, "")
        elif f in (5, 6):
            value = _text(v)
        elif f == 7:                 # a string kept as a stat-metadata name
            value = stat_names.get(v, "")
        elif f in (2, 3, 4):
            value = v
    return name, value


def op_tags(path: str) -> Dict[Tuple[str, str], Optional[str]]:
    """(program id, instruction) -> ``tf_op`` of every op described on a
    TPU plane of the trace (None where the op carries none)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    tags: Dict[Tuple[str, str], Optional[str]] = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        parts = defaultdict(list)
        for g, v in _fields(plane):
            parts[g].append(v)
        name = _text(parts[2][0]) if parts[2] else ""
        if not re.fullmatch(r"/device:TPU:\d+", name):
            continue
        stat_names = {k: _text(dict(_fields(v)).get(2, b""))
                      for k, v in _map_entries(parts[5])}
        for _, md in _map_entries(parts[4]):
            fields = list(_fields(md))
            stats = dict(_stat(v, stat_names) for g, v in fields if g == 5)
            if "program_id" not in stats:
                continue
            text = next((_text(v) for g, v in fields if g == 2), "")
            tags[(str(stats["program_id"]), tr.op_name(text))] = \
                stats.get("tf_op")
    return tags


# ---------------------------------------------------------------------------
# the traced window
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ev = tr.events_from_profile(pd)
    modules, threads = {}, []
    for plane in pd.planes:
        if plane.name in ev["ops"]:
            for line in plane.lines:
                if line.name == tr.MODULES_LINE:
                    modules[plane.name] = [
                        (e.name, e.start_ns + ev["shift_ns"],
                         e.end_ns + ev["shift_ns"]) for e in line.events]
        elif plane.name.startswith("/host:"):
            threads.extend([(e.name, e.start_ns, e.end_ns)
                            for e in line.events] for line in plane.lines)
    windows = [h for h in ev["host"] if h[0] == tr.WINDOW_SPAN]
    w = max(windows, key=lambda h: h[2] - h[1]) if windows else None
    return {"ev": ev, "modules": modules, "threads": threads,
            "window": (w[1], w[2]) if w else None,
            "tags": op_tags(path)}


def load(path: str) -> dict:
    return _load(path, os.stat(path).st_mtime_ns)


def _window_ops(ops, window):
    clipped = (tr._clip(o, window) for o in ops)
    return sorted((c for c in clipped if c), key=lambda o: (o[1], -o[2]))


def split_by_scope(ops, modules, tags, window) -> dict:
    """Self time in ``window`` (s, averaged over devices) by scope and by
    (module, instruction), and busy time. ``ops`` and ``modules`` hold
    each device's ``XLA Ops`` and ``XLA Modules`` events, ``tags`` is
    :func:`op_tags`."""
    by_scope: Dict[str, float] = defaultdict(float)
    by_op: Dict[Tuple[str, str], List] = {}
    busy = 0.0
    for dev, dev_ops in ops.items():
        mods = sorted(modules.get(dev, []), key=lambda m: m[1])
        starts = [s for _, s, _ in mods]
        inside = _window_ops(dev_ops, window)
        busy += tr.measure([(s, e) for _, s, e in inside]) / len(ops)
        self_t, _ = tr.nesting(inside)
        for (name, s, _), dt in zip(inside, self_t):
            k = bisect.bisect_right(starts, s) - 1
            module = mods[k][0] if k >= 0 and s < mods[k][2] else ""
            pid = _PROGRAM_ID.search(module)
            scope = scope_of(tags.get((pid.group(1) if pid else "", name)))
            by_scope[scope or UNSCOPED] += dt * 1e-9 / len(ops)
            entry = by_op.setdefault((module.split("(", 1)[0], name),
                                     [scope, 0.0])
            entry[1] += dt * 1e-9 / len(ops)
    return {"window_s": (window[1] - window[0]) * 1e-9,
            "busy_s": busy * 1e-9, "by_scope": dict(by_scope),
            "by_op": by_op}


def _intersect(a, b):
    return tr.subtract(a, tr.subtract(a, b))


def _exclusive(spans):
    """Each span's interval less those of the spans nested in it (spans
    of one thread nest or are disjoint)."""
    spans = sorted(spans, key=lambda h: (h[1], -h[2]))
    children = defaultdict(list)
    stack: List[int] = []
    for i, (_, s, e) in enumerate(spans):
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            children[stack[-1]].append((s, e))
        stack.append(i)
    return [(n, tr.subtract([(s, e)], children[i]))
            for i, (n, s, e) in enumerate(spans)]


def split_idle(dev_ops, caller, window) -> Optional[dict]:
    """Idle of one device in ``window`` (s): in all, inside the
    ``repro.bench_comm`` spans of ``caller`` (one thread's host spans),
    and by the caller's innermost ``repro.*`` span (``(no region)``
    outside every one); None if the caller opened no
    ``repro.bench_comm`` in the window."""
    idle = tr.subtract([window],
                       [(s, e) for _, s, e in _window_ops(dev_ops, window)])
    regions = [c for c in (tr._clip(h, window) for h in caller
                           if h[0].startswith(REGION_PREFIX)) if c]
    comm = [(s, e) for n, s, e in regions if n == BENCH_COMM]
    if not comm:
        return None
    by_region: Dict[str, float] = defaultdict(float)
    for name, own in _exclusive(regions):
        by_region[name[len(REGION_PREFIX):]] += \
            tr.measure(_intersect(idle, own)) * 1e-9
    outside = tr.subtract(idle, [(s, e) for _, s, e in regions])
    by_region[OUTSIDE] = tr.measure(outside) * 1e-9
    return {"window_s": (window[1] - window[0]) * 1e-9,
            "idle_s": tr.measure(idle) * 1e-9,
            "in_bench_comm_s": tr.measure(_intersect(idle, comm)) * 1e-9,
            "by_region": dict(by_region)}


def scope_times(path: str) -> Optional[dict]:
    """:func:`split_by_scope` of a trace file; None without a window or
    a device."""
    t = load(path)
    if t["window"] is None or not t["ev"]["ops"]:
        return None
    return split_by_scope(t["ev"]["ops"], t["modules"], t["tags"],
                          t["window"])


def region_idle(path: str) -> Optional[dict]:
    """:func:`split_idle` of device 0 of a trace file, on the line of the
    thread that holds the window."""
    t = load(path)
    w, ops = t["window"], t["ev"]["ops"]
    if w is None or not ops:
        return None
    caller = next((line for line in t["threads"] if any(
        n == tr.WINDOW_SPAN and (s, e) == w for n, s, e in line)), None)
    return split_idle(ops[min(ops)], caller, w) if caller else None


# ---------------------------------------------------------------------------
# what the metric readers call
# ---------------------------------------------------------------------------

def _trace_of(run) -> Optional[str]:
    """The run's trace file, or None outside a traced run or where the
    file on disk is not the one the harness reduced (another window)."""
    if run.trace is None or not run.trace_units.get("steps"):
        return None
    try:
        path = tr.find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    w = load(path)["window"]
    if w is None or (w[1] - w[0]) * 1e-9 != run.trace["window_s"]:
        return None
    return path


def scope_ms_per_step(run, scope: str) -> Optional[float]:
    """Device self time per stencil step of the ops in ``scope``, in ms;
    None where no op of the window carries the scope."""
    path = _trace_of(run)
    times = scope_times(path) if path else None
    if not times or scope not in times["by_scope"]:
        return None
    return times["by_scope"][scope] / run.trace_units["steps"] * 1e3


def region_idle_ms_per_step(run) -> Optional[float]:
    """Device-0 idle per stencil step inside ``repro.bench_comm``, in ms."""
    path = _trace_of(run)
    idle = region_idle(path) if path else None
    if idle is None:
        return None
    return idle["in_bench_comm_s"] / run.trace_units["steps"] * 1e3


def report(path: str, steps: int) -> dict:
    """Both splits per step (ms), and the ops of at least 1% of busy time
    with their scope."""
    per = 1e3 / steps
    out = {"steps": steps}
    times = scope_times(path)
    if times:
        busy = times["busy_s"]
        out["busy_ms"] = busy * per
        out["window_ms"] = times["window_s"] * per
        out["by_scope_ms"] = {k: v * per for k, v in
                              sorted(times["by_scope"].items())}
        out["self_sum_ms"] = sum(times["by_scope"].values()) * per
        out["ops_over_1pct"] = sorted(
            ([m, i, sc, s * per] for (m, i), (sc, s) in
             times["by_op"].items() if s >= 0.01 * busy),
            key=lambda r: -r[3])
    idle = region_idle(path)
    if idle:
        out["idle_ms"] = idle["idle_s"] * per
        out["region_idle_ms"] = idle["in_bench_comm_s"] * per
        out["idle_by_region_ms"] = {k: v * per for k, v in sorted(
            idle["by_region"].items(), key=lambda kv: -kv[1])}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir", nargs="?", default=TRACE_DIR)
    ap.add_argument("--steps", type=int, required=True,
                    help="stencil steps in the traced window")
    args = ap.parse_args(argv)
    print(json.dumps(report(tr.find_xplane(args.trace_dir), args.steps)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
