"""The measurement command refuses the CPU, and a checkout without the
program; a configuration, a traffic mix and a metric added as new files
plus new ``BENCHMARK.json`` entries are found by name."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from perfbench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]


def _command(cwd, workload="nyx-256-compress"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", workload, "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(proc):
    return not any(line.lstrip().startswith("{") for line in proc.stdout.splitlines())


@pytest.mark.parametrize("workload", [tiny.NYX, tiny.EEG])
def test_refuses_the_cpu(workload):
    proc = _command(tiny.eeg_root() if workload == tiny.EEG else ROOT, workload)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_fails_with_only_the_benchmark(tmp_path):
    tiny.copy_benchmark(tmp_path)
    proc = _command(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc)


TOY_GEN = '''
import numpy as np


def make(cfg, size, index, seed):
    rng = np.random.default_rng([seed, index])
    edge = int(cfg["edge"])
    return np.cumsum(rng.standard_normal((edge, edge)), axis=1).astype(np.float32)
'''

TOY_METRIC = '''
def read(run):
    done = run.window.completed
    return sum(len(r.resp.payload) for r in done) / len(done) / 1e3 if done else None
'''


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    tiny.copy_benchmark(tmp_path)
    pb = tmp_path / "perfbench"
    cfg = json.loads((pb / "configs" / "nyx.json").read_text())
    cfg.update(name="toy", edge=32, reduced={}, check_sample=2)
    (pb / "configs" / "toy.json").write_text(json.dumps(cfg))
    (pb / "configs" / "toy.py").write_text(TOY_GEN)
    (pb / "traffic" / "pair.json").write_text(json.dumps({"loop": "closed", "in_flight": 2}))
    (pb / "metrics" / "blob_kB.py").write_text(TOY_METRIC)
    tiny.add_entries(tmp_path, {
        "configs": [{"name": "toy", "source": "a 2-D random walk", "reduced": [],
                     "file": "perfbench/configs/toy.json", "why": "a test"}],
        "workloads": [{"name": "toy-pair", "config": "toy", "traffic": "pair", "chips": 1,
                       "why": "a test"}],
        "end_to_end": [{"name": "blob_kB", "unit": "kB", "better": "lower", "bound": 0.01,
                        "source": "host_clock", "workloads": ["toy-pair"]}],
    })
    run, line = tiny.run("toy-pair", root=tmp_path)
    tiny.assert_well_formed(line, run)
    assert set(line["metrics"]) == {"blob_kB", "ratio", "setup_s"}
    assert line["correct"] is True
    assert run.window.requests[0].resp.payload[:4] == b"FFCZ"
