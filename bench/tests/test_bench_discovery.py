"""Cells, configurations, traffic and metric readers are found by the
names BENCHMARK.json gives them: a new cell is new files and entries."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.tests import tiny
from bench import harness

ROOT = tiny.ROOT


def _root_with_new_cell(tmp_path):
    """A checkout whose BENCHMARK.json gains a cell with its own
    configuration, traffic, limits and per-layer metric files."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    conf = json.load(open(os.path.join(ROOT, "bench", "configs",
                                       "yi-6b-train.json")))
    conf["name"] = "yi-6b-train-short"
    (tmp_path / "bench" / "configs" / "yi-6b-train-short.json").write_text(
        json.dumps(conf))
    traffic = json.load(open(harness.traffic_path("train-2048x1")))
    traffic["seq"] = 1024
    (tmp_path / "bench" / "traffic" / "train-1024x1.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "limits" / "yi6b-train-short.json").write_text(
        json.dumps({"limits": {"loss_gap": 1, "grad_norm_gap": 1,
                               "update_norm_gap": 1}}))
    (tmp_path / "bench" / "metrics" / "steps_done.py").write_text(
        "KERNELS = ()\n\ndef read(r):\n    return r.steps\n")
    spec["configs"].append({"name": "yi-6b-train-short",
                            "source": "https://example.org/config.json",
                            "file": "bench/configs/yi-6b-train-short.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "yi6b-train-short",
                              "config": "yi-6b-train-short",
                              "traffic": "train-1024x1", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0]["workloads"].append("yi6b-train-short")
    spec["per_layer"].append({"name": "steps_done", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "train step", "moves": "train_tok_s",
                              "workloads": ["yi6b-train-short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def test_new_cell_is_found_by_name(tmp_path):
    root = str(_root_with_new_cell(tmp_path))
    cell = harness.find_cell("yi6b-train-short", root=root)
    assert cell.config["name"] == "yi-6b-train-short"
    assert cell.traffic["seq"] == 1024
    assert cell.limits["loss_gap"] == 1
    assert [m["name"] for m in cell.end_to_end] == ["train_tok_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["steps_done"]
    assert harness.traffic_kind(cell.traffic["kind"]).__name__ == \
        "bench.kinds.train"

    class R:
        steps = 7
    assert harness.reader("steps_done", root=root).read(R()) == 7


def test_every_cell_of_the_benchmark_has_its_files():
    spec = harness.spec()
    for w in spec["workloads"]:
        cell = harness.find_cell(w["name"])
        assert harness.traffic_kind(cell.traffic["kind"])
        assert cell.limits
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert hasattr(harness.reader(m["name"]), "read")
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}


@pytest.mark.parametrize("where", ["cpu", "bare"])
def test_no_result_without_chip_or_program(tmp_path, where):
    """Without a TPU, or in a directory that holds only the benchmark's
    files, a run exits non-zero and prints no result."""
    if where == "bare":
        shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
        cwd = str(tmp_path)
    else:
        cwd = ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "yi6b-train-1chip", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert ("no TPU" in p.stderr) if where == "cpu" else \
        ("src/repro" in p.stderr)
