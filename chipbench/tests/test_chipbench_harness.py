"""The harness finds every cell's files by name, picks up cells, configs,
drivers and metrics added as new files, and refuses to run without a
TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import bench

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_workload_resolves_by_name():
    b = _benchmark()
    files = {c["name"]: c["file"] for c in b["configs"]}
    for w in b["workloads"]:
        cell = bench.resolve(w["name"], b)
        assert cell.config["name"] == w["config"]
        assert files[w["config"]] == f"chipbench/configs/{w['config']}.json"
        assert hasattr(cell.driver, "build")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        assert set(cell.readers) == names | {m["name"] for m in
                                             cell.per_layer}


def test_benchmark_names_and_keys():
    b = _benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for entry in b["configs"] + b["workloads"] + b["end_to_end"] + \
            b["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for m in b["end_to_end"]:
        assert 0.0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]


def _copy_bench(tmp_path):
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return tmp_path / "chipbench"


TOY_DRIVER = '''
class Toy:
    extras = {}

    def __init__(self, ctx):
        self.n = ctx.config["work"]

    def call(self):
        return {"items": self.n}

    def check(self):
        return [("toy_err", 0.0, 1.0)]


def build(ctx):
    return Toy(ctx)
'''
TOY_METRIC = '''
def read(run):
    return run.units["items"] / run.window_s
'''


def test_new_files_only_add_a_cell(tmp_path):
    """A configuration, a traffic mix, a driver and a per-layer metric,
    each as a new file; no existing file is edited but BENCHMARK.json."""
    bench_dir = _copy_bench(tmp_path)
    before = {p: p.read_bytes() for p in bench_dir.rglob("*")
              if p.is_file()}
    (bench_dir / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "driver": "toy", "work": 3, "reduced": []}))
    (bench_dir / "traffic" / "toy-mix.json").write_text(
        json.dumps({"trace_calls": 1}))
    (bench_dir / "drivers" / "toy.py").write_text(TOY_DRIVER)
    (bench_dir / "metrics" / "toy_rate.py").write_text(TOY_METRIC)
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "toy-cell", "config": "toy",
                           "traffic": "toy-mix", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "toy_rate", "unit": "items/s",
                           "better": "higher", "source": "program_counter",
                           "layer": "toy", "moves": "setup_s",
                           "workloads": ["toy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    for p, data in before.items():
        assert p.read_bytes() == data

    cell = bench.resolve("toy-cell", b, bench_dir=str(bench_dir))
    assert cell.traffic == {"trace_calls": 1}
    assert "toy_rate" in cell.readers
    import jax
    result = bench.run_cell(cell, 7, 0.05, False, jax.devices()[:1],
                            time.perf_counter(), str(tmp_path / "trace"),
                            log=lambda m: None)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s"}
    assert result["attempted"] > 0
    # the per-layer reader runs in a traced run; here it reads the record
    run = bench.Run("toy-cell", cell.config, cell.traffic, 1, 0.0, 0.5,
                    [0.5], {"items": 3}, {}, {})
    assert cell.readers["toy_rate"](run) == 6.0


def _run_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "halo512-fused-1chip", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_cpu_platform_is_refused():
    proc = _run_cmd(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    _copy_bench(tmp_path)
    proc = _run_cmd(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout


@pytest.mark.parametrize("name", ["halo512-profiled-1chip",
                                  "halo512-fused-1chip",
                                  "halo512-fused-2x2"])
def test_each_cell_has_its_traffic_and_trace_length(name):
    cell = bench.resolve(name)
    assert cell.traffic["trace_calls"] >= 1
    assert cell.chips in (1, 4)
