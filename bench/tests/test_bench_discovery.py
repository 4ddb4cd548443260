"""A new cell and a new per-layer metric are files and entries only: the
harness finds each by name in a temporary checkout and runs them."""

import json

import pytest

from bench.harness import Benchmark
from bench.tests import common
from bench.tests.tiny import CONFIGS, WORKLOADS, make_root

NEW_CELL = "tiny-smollm.train-stream"
NEW_METRIC = "window_steps"


@pytest.fixture
def root(tmp_path):
    workloads = {**WORKLOADS, NEW_CELL: {**WORKLOADS["tiny-olmo.train-stream"],
                                         "config": "tiny-smollm"}}
    root = make_root(tmp_path, CONFIGS, workloads)
    (root / "bench" / "metrics" / f"{NEW_METRIC}.py").write_text(
        "def read(r):\n"
        "    steps = r.spans.get('step_dispatch')\n"
        "    return float(len(steps)) if steps else None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": NEW_METRIC, "unit": "steps", "better": "higher",
                              "source": "program_span", "layer": "loader",
                              "moves": "train_tokens_per_s", "workloads": [NEW_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_cell_and_metric_found_by_name(root):
    bench = Benchmark(root)
    cell = bench.cell(NEW_CELL, 5)
    assert cell.config["name"] == "tiny-smollm" and cell.workload["driver"] == "train"
    assert bench.driver(cell).run.__module__.endswith("train")
    assert NEW_METRIC in [m["name"] for m in bench.per_layer(NEW_CELL)]
    assert NEW_METRIC not in [m["name"] for m in bench.per_layer("tiny-olmo.train-stream")]
    assert bench.reader(NEW_METRIC).read is not None
    with pytest.raises(FileNotFoundError):
        bench.reader("no_such_metric")


def test_new_cell_runs_and_reports_the_new_metric(root, monkeypatch):
    common.fake_trace(monkeypatch)
    result = common.run(root, NEW_CELL, trace=True)
    assert result["correct"], result["checks"]
    assert result["metrics"][NEW_METRIC]["value"] == result["attempted"]


def test_command_refuses_a_machine_without_a_chip():
    import os
    import subprocess
    import sys

    from bench.tests.tiny import ROOT

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", "smollm-135m.coldstart",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
