"""Device time by program scope and device idle by program region
(``chipbench/scopes.py`` and the readers of ``halo_interior_ms``,
``halo_boundary_ms`` and ``region_idle_ms``), on hand-made events with
known answers and on the recorded ``data/small.xplane.pb``.

Hand-made scopes (ns; device 0, window 0-180):

    module jit_local(11) 0-100:   fusion.4 10-40 (halo.interior),
                                  copy-start 40-45 (no tf_op)
    module jit_boundary(22) 120-200: fusion.4 130-150 (halo.boundary),
                                  while.1 150-190 (halo.boundary)
                                  holding fusion.9 160-170 (halo.interior)

The same instruction name in two modules takes each module's scope.
Clipped to the window, while.1 is 150-180 and its self time 30 - 10 =
20. Device 0: interior 30 + 10 = 40, boundary 20 + 20 = 40, unscoped 5,
busy 35 + 50 = 85. Device 1 holds fusion.4 10-30 of jit_local(11) only:
interior 20, busy 20. Averaged: interior 30, boundary 20, unscoped 2.5,
busy 52.5.

Hand-made regions (ns; window 0-100): device ops 10-20, 40-60, 90-95, so
idle 0-10, 20-40, 60-90, 95-100 = 65. Caller spans: cycle_0 5-70 holding
bench_comm 6-69, which holds post-comm 15-35 and wait-recv 35-45; then
wait-send 70-92. Idle by innermost region: post-comm 20-35 = 15,
wait-recv 35-40 = 5, bench_comm 6-10 + 60-69 = 13, cycle_0 5-6 + 69-70 =
2, wait-send 70-90 = 20, no region 0-5 + 95-100 = 10; inside bench_comm
15 + 5 + 13 = 33.

``data/halo_small.xplane.pb`` was recorded on a TPU v5e by
``tools/record_scope_trace.py``: on a 32^3 box, two calls of the
segmented ``HaloProgram.run(steps=2)`` with an ``incoming`` engine,
then one call of the fused program of 2 steps (6 steps), with the
regions mirrored as ``repro.*`` spans. Its modules are ``jit_extract``,
``jit_exchange``, ``jit_interior``, ``jit_boundary`` and ``jit_local``;
the only ops without a halo scope are copies XLA added (``copy-start``,
``copy-done``, layout ``copy.N``). The numbers pinned below are what
``scopes`` read from it when it was recorded, and they reconcile with
``trace_reduce.reduce_file`` of the same file.
"""
import os

import pytest

from chipbench import bench, scopes
from chipbench import trace_reduce as tr

DATA_DIR = os.path.join(os.path.dirname(__file__), "data")
DATA = os.path.join(DATA_DIR, "small.xplane.pb")
HALO = os.path.join(DATA_DIR, "halo_small.xplane.pb")
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"

OPS = {DEV0: [("fusion.4", 10, 40), ("copy-start", 40, 45),
              ("fusion.4", 130, 150), ("while.1", 150, 190),
              ("fusion.9", 160, 170)],
       DEV1: [("fusion.4", 10, 30)]}
MODULES = {DEV0: [("jit_local(11)", 0, 100), ("jit_boundary(22)", 120, 200)],
           DEV1: [("jit_local(11)", 0, 100)]}
TAGS = {("11", "fusion.4"): "jit(local)/halo.interior/add:add",
        ("11", "copy-start"): None,
        ("22", "fusion.4"): "jit(boundary)/halo.boundary/scatter:scatter",
        ("22", "while.1"): "jit(boundary)/halo.boundary/while:while",
        ("22", "fusion.9"): "jit(boundary)/halo.interior/add:add"}

IDLE_OPS = [("fusion.1", 10, 20), ("fusion.2", 40, 60), ("fusion.3", 90, 95)]
CALLER = [("chipbench.window", 0, 100), ("repro.cycle_0", 5, 70),
          ("repro.bench_comm", 6, 69), ("repro.post-comm", 15, 35),
          ("$halo.py:230 step", 15, 35), ("repro.wait-recv", 35, 45),
          ("repro.wait-send", 70, 92)]


def test_scope_of():
    assert scopes.scope_of("jit(local)/halo.interior/add:add") == \
        "halo.interior"
    assert scopes.scope_of(
        "jit(exchange)/halo.exchange/comm_ppermute_x/ppermute") == \
        "halo.exchange"
    assert scopes.scope_of("jit(<lambda>)/dot_general:") is None
    assert scopes.scope_of(None) is None


def test_split_by_scope_hand_made():
    out = scopes.split_by_scope(OPS, MODULES, TAGS, (0, 180))
    got = {k: round(v * 1e9, 6) for k, v in out["by_scope"].items()}
    assert got == {"halo.interior": 30.0, "halo.boundary": 20.0,
                   scopes.UNSCOPED: 2.5}
    assert out["busy_s"] * 1e9 == pytest.approx(52.5)
    assert out["window_s"] * 1e9 == pytest.approx(180)
    assert sum(out["by_scope"].values()) == pytest.approx(out["busy_s"])
    by_op = {k: (sc, round(s * 1e9, 6)) for k, (sc, s) in
             out["by_op"].items()}
    assert by_op[("jit_local", "fusion.4")] == ("halo.interior", 25.0)
    assert by_op[("jit_boundary", "fusion.4")] == ("halo.boundary", 10.0)
    assert by_op[("jit_boundary", "while.1")] == ("halo.boundary", 10.0)
    assert by_op[("jit_local", "copy-start")] == (None, 2.5)


def test_split_idle_hand_made():
    out = scopes.split_idle(IDLE_OPS, CALLER, (0, 100))
    assert out["idle_s"] * 1e9 == pytest.approx(65)
    assert out["in_bench_comm_s"] * 1e9 == pytest.approx(33)
    got = {k: round(v * 1e9, 6) for k, v in out["by_region"].items()}
    assert got == {"post-comm": 15.0, "wait-recv": 5.0, "bench_comm": 13.0,
                   "cycle_0": 2.0, "wait-send": 20.0, scopes.OUTSIDE: 10.0}
    assert sum(out["by_region"].values()) == pytest.approx(out["idle_s"])
    no_comm = [h for h in CALLER if h[0] != "repro.bench_comm"]
    assert scopes.split_idle(IDLE_OPS, no_comm, (0, 100)) is None


def _run(trace, steps=4):
    return bench.Run("halo512-profiled-1chip", {}, {}, 1, 0.0, 1.0, [1.0],
                     {"steps": steps}, {}, {}, [], trace, {"steps": steps})


def test_readers_on_hand_made_trace(tmp_path, monkeypatch):
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    loaded = {"ev": {"ops": {DEV0: IDLE_OPS}, "host": [], "shift_ns": 0},
              "modules": {DEV0: [("jit_step(5)", 0, 100)]},
              "tags": {("5", "fusion.1"): "jit(step)/halo.interior/add:",
                       ("5", "fusion.2"): "jit(step)/halo.boundary/add:"},
              "threads": [[("repro.BlockingProgress lock", 30, 50),
                           ("repro.bench_comm", 0, 100)], CALLER],
              "window": (0, 100)}
    monkeypatch.setattr(scopes, "load", lambda path: loaded)
    monkeypatch.setattr(scopes, "TRACE_DIR", str(tmp_path))
    cell = bench.resolve("halo512-profiled-1chip")
    run = _run({"window_s": (100 - 0) * 1e-9})
    read = cell.readers
    assert read["halo_interior_ms.profiled"](run) == pytest.approx(10e-6 / 4)
    assert read["halo_boundary_ms.profiled"](run) == pytest.approx(20e-6 / 4)
    assert read["region_idle_ms.profiled"](run) == pytest.approx(33e-6 / 4)
    # a trace on disk that is not the one the harness reduced
    assert read["halo_interior_ms.profiled"](_run({"window_s": 1.0})) is None
    # an untraced run
    assert read["region_idle_ms.profiled"](_run(None)) is None
    # no op carries the scope asked for
    assert scopes.scope_ms_per_step(run, "halo.exchange") is None


def test_op_tags_from_recorded_trace():
    pid = "16013892145657761946"
    assert scopes.op_tags(DATA) == {
        (pid, "copy-start"): None, (pid, "copy-done"): None,
        (pid, "convolution_sine_fusion"): "jit(<lambda>)/dot_general:"}


def test_recorded_trace_has_no_scope_and_no_region(monkeypatch):
    """A program that names no halo scope and opens no region (as the
    recorded one): every reader returns None, never 0."""
    times = scopes.scope_times(DATA)
    assert times["by_scope"] == {scopes.UNSCOPED: pytest.approx(23520e-9)}
    assert times["busy_s"] == pytest.approx(23520e-9, abs=1e-15)
    assert times["by_op"][("jit__lambda", "convolution_sine_fusion")][1] \
        == pytest.approx((7825 + 7825 + 7823) * 1e-9)
    assert scopes.region_idle(DATA) is None
    monkeypatch.setattr(tr, "find_xplane", lambda d: DATA)
    run = _run(tr.reduce_file(DATA), steps=3)
    for cell_name, suffix in (("halo512-profiled-1chip", "profiled"),
                              ("halo512-fused-1chip", "1chip"),
                              ("halo512-fused-2x2", "2x2")):
        cell = bench.resolve(cell_name)
        for base in ("halo_interior_ms", "halo_boundary_ms"):
            assert cell.readers[f"{base}.{suffix}"](run) is None
    cell = bench.resolve("halo512-profiled-1chip")
    assert cell.readers["region_idle_ms.profiled"](run) is None
    report = scopes.report(DATA, 3)
    assert report["self_sum_ms"] == pytest.approx(report["busy_ms"])
    assert "region_idle_ms" not in report


def test_recorded_halo_trace_by_scope():
    times = scopes.scope_times(HALO)
    got = {k: round(v * 1e9, 3) for k, v in times["by_scope"].items()}
    assert got == {"halo.extract": 15848.0, "halo.exchange": 9469.0,
                   "halo.interior": 29330.0, "halo.boundary": 56194.0,
                   scopes.UNSCOPED: 26541.0}
    reduced = tr.reduce_file(HALO)
    assert times["busy_s"] == pytest.approx(reduced["busy_s"], abs=1e-15)
    assert sum(times["by_scope"].values()) == \
        pytest.approx(reduced["busy_s"], rel=1e-9)
    by_op = times["by_op"]
    assert by_op[("jit_interior", "fusion.4")] == \
        ["halo.interior", pytest.approx(6644e-9)]
    assert by_op[("jit_boundary", "copy")][0] == "halo.boundary"
    modules = {m for m, _ in by_op}
    assert modules == {"jit_extract", "jit_exchange", "jit_interior",
                       "jit_boundary", "jit_local"}
    unscoped = {name for (_, name), (scope, _) in by_op.items()
                if scope is None}
    assert unscoped and all(n.startswith("copy") for n in unscoped)
    for (module, name), (scope, _) in by_op.items():
        if name.startswith("collective-permute"):
            assert scope == "halo.exchange", (module, name)


def test_recorded_halo_trace_by_region(monkeypatch):
    idle = scopes.region_idle(HALO)
    reduced = tr.reduce_file(HALO)
    assert idle["idle_s"] == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], abs=1e-12)
    assert idle["in_bench_comm_s"] * 1e9 == pytest.approx(11011826.0)
    by_region = {k: round(v * 1e9, 3) for k, v in idle["by_region"].items()}
    assert by_region["MPI_Wait"] == 3665760.0
    assert by_region["post-comm"] == 2927699.0
    assert by_region[scopes.OUTSIDE] == 641354.0
    assert sum(idle["by_region"].values()) == pytest.approx(idle["idle_s"])
    inside = ("bench_comm", "pre-comm", "post-send", "MPI_Isend",
              "BlockingProgress lock", "post-comm", "wait-recv",
              "MPI_Wait", "post-recv")
    assert sum(idle["by_region"][k] for k in inside) == \
        pytest.approx(idle["in_bench_comm_s"])

    monkeypatch.setattr(tr, "find_xplane", lambda d: HALO)
    run = _run(reduced, steps=6)
    read = bench.resolve("halo512-profiled-1chip").readers
    assert read["halo_interior_ms.profiled"](run) == \
        pytest.approx(29330e-9 / 6 * 1e3)
    assert read["halo_boundary_ms.profiled"](run) == \
        pytest.approx(56194e-9 / 6 * 1e3)
    assert read["region_idle_ms.profiled"](run) == \
        pytest.approx(11011826e-9 / 6 * 1e3)
