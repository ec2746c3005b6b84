"""The harness finds every configuration, traffic mix, runner and
per-layer metric by the name BENCHMARK.json gives, so a new cell takes
only new files and entries; the command refuses to run without a chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import tiny

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

READER = '''
def read(ctx):
    return ctx.get("answer")
'''


@pytest.fixture
def dummy_root(tmp_path):
    """The tiny tree plus a dummy configuration, mix, cell and two
    metrics, added as new files and entries only."""
    root = str(tmp_path)
    bench = tiny.write(root)
    dummy_cfg = dict(tiny.CONFIG, num_layers=1)
    with open(os.path.join(root, "bench", "configs", "dummy.json"),
              "w") as f:
        json.dump(dummy_cfg, f)
    with open(os.path.join(root, "bench", "traffic", "dummy-mix.json"),
              "w") as f:
        json.dump(dict(tiny.MIX, topics=2), f)
    for name in ("dummy_metric.train", "other_metric.train"):
        with open(os.path.join(root, "bench", "metrics", f"{name}.py"),
                  "w") as f:
            f.write(READER)
    bench["configs"].append({"name": "dummy", "source": "test",
                             "file": "bench/configs/dummy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy.train.dummy-mix",
                               "config": "dummy", "traffic": "dummy-mix",
                               "chips": 1, "why": "test"})
    bench["per_layer"] += [
        {"name": "dummy_metric.train", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "test",
         "moves": "train_tokens_per_s"},
        {"name": "other_metric.train", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "test",
         "moves": "train_tokens_per_s", "workloads": [tiny.CELL]}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_cell_resolves_every_name(dummy_root):
    cell = harness.Cell(harness.load_benchmark(dummy_root),
                        "dummy.train.dummy-mix", root=dummy_root)
    assert cell.config["num_layers"] == 1
    assert cell.traffic["topics"] == 2
    assert cell.chips == 1
    from bench import train_cell
    assert cell.runner() is train_cell
    assert [m["name"] for m in cell.end_to_end()] == [
        "train_tokens_per_s", "setup_s"]


def test_per_layer_metrics_by_name_and_workloads(dummy_root):
    bench = harness.load_benchmark(dummy_root)
    dummy = harness.Cell(bench, "dummy.train.dummy-mix", root=dummy_root)
    cell = harness.Cell(bench, tiny.CELL, root=dummy_root)
    assert [m["name"] for m in dummy.per_layer()] == ["dummy_metric.train"]
    assert harness.read_per_layer(dummy, {"answer": 42}) == {
        "dummy_metric.train": {"value": 42.0, "unit": "%"}}
    got = harness.read_per_layer(cell, {"answer": 7})
    assert set(got) == {"dummy_metric.train", "other_metric.train"}
    # a reader that finds nothing to read leaves its metric out
    assert harness.read_per_layer(cell, {}) == {}


def test_unknown_names_are_errors(dummy_root):
    bench = harness.load_benchmark(dummy_root)
    with pytest.raises(harness.BenchError, match="workload"):
        harness.Cell(bench, "no.such.cell", root=dummy_root)
    with pytest.raises(harness.BenchError, match="reader"):
        harness.load_reader("no_such_metric", root=dummy_root)


def test_every_metric_of_the_benchmark_has_a_reader():
    bench = harness.load_benchmark()
    for m in bench["per_layer"]:
        assert hasattr(harness.load_reader(m["name"]), "read"), m["name"]
    for w in bench["workloads"]:
        cell = harness.Cell(bench, w["name"])
        assert cell.runner().run


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "gpt-moe-s.train.zipf-topics-drift", "--seed", str(2**35 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu():
    r = _run(REPO)
    assert r.returncode == 2
    assert r.stdout.strip() == ""
    assert "CPU" in r.stderr


def test_command_fails_with_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
