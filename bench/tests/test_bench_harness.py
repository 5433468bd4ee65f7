"""The harness finds every part of a cell by name, and refuses to run
without a chip."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def _copy_benchmark(dst: Path):
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))


def test_every_committed_cell_resolves():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        assert cell.config["name"] == w["config"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.load_reader(ROOT, m["name"]))


def test_a_cell_of_new_files_loads_without_code_edits(tmp_path):
    _copy_benchmark(tmp_path)
    cfg = json.loads((tmp_path / "bench/configs/gas16-w160k.json").read_text())
    cfg.update(name="toy-w8k", window=8000)
    (tmp_path / "bench/configs/toy-w8k.json").write_text(json.dumps(cfg))
    traffic = json.loads((tmp_path / "bench/traffic/slide.json").read_text())
    traffic["block"] = 256
    (tmp_path / "bench/traffic/slide256.json").write_text(json.dumps(traffic))
    (tmp_path / "bench/metrics/toy_steps.py").write_text(
        "def read(run):\n    return float(len(run.steps))\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy-w8k", "source": "https://arxiv.org/abs/2412.07789",
                            "file": "bench/configs/toy-w8k.json", "reduced": ["window"],
                            "why": "a small window"})
    spec["workloads"].append({"name": "toy-w8k.slide256", "config": "toy-w8k",
                              "traffic": "slide256", "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append("toy-w8k.slide256")
    spec["per_layer"].append({"name": "toy_steps", "unit": "1", "better": "higher",
                              "source": "host_clock", "layer": "online summary",
                              "moves": "ingest_ops_per_s", "workloads": ["toy-w8k.slide256"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell(tmp_path, "toy-w8k.slide256")
    assert cell.config["window"] == 8000 and cell.traffic["block"] == 256
    assert [m["name"] for m in cell.end_to_end] == ["ingest_ops_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["toy_steps"]
    run = harness.Run(steps=[[0.0, 512, 1.0]] * 3)
    assert harness.load_reader(tmp_path, "toy_steps")(run) == 3.0


def _run_command(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gas16-w160k.slide", "--seed",
         str(2**31 + 99), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("tree", ["checkout", "benchmark-only"])
def test_command_without_a_tpu_prints_no_result(tree, tmp_path):
    if tree == "checkout":
        cwd = ROOT
    else:
        _copy_benchmark(tmp_path)
        cwd = tmp_path
    p = _run_command(cwd)
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{") for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr or "missing" in p.stderr
