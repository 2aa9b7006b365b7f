"""CPU rehearsal helpers: each cell at a tiny size through the harness API."""

from __future__ import annotations

import atexit
import json
import math
import shutil
import tempfile
from pathlib import Path

from perfbench import harness

NYX = "nyx-256-compress"
EEG = "eeg-pencils-steady"

#: configuration keys shrunk for the CPU; the code paths are the chip's
OVERRIDES = {
    NYX: {"edge": 16, "check_sample": 2},
    EEG: {"channels": 4, "block": 64, "pool_per_size": 2, "check_sample": 8,
          "service": {"base": "szlike", "pipeline_depth": 2, "max_batch": 2, "block": 64}},
}
TRAFFIC = {NYX: {}, EEG: {"rate_per_s": 25.0, "sizes": {"1": 0.5, "2": 0.5}, "ramp_s": 0.5}}

#: The open-loop pencil cell was measured on the chip and left out of
#: BENCHMARK.json, because its tail spreads wider than any bound allows
#: (PERF.md, Open questions).  Its files stay under perfbench/; the
#: rehearsals run it from a copy of the benchmark with these entries added.
EEG_ENTRIES = {
    "configs": [{"name": "eeg", "source": "https://physionet.org/content/eegmmidb/1.0.0/",
                 "file": "perfbench/configs/eeg.json", "reduced": [],
                 "why": "many small channels x time windows"}],
    "workloads": [{"name": EEG, "config": "eeg", "traffic": "steady", "chips": 1,
                   "why": "open Poisson loop of 64-channel windows"}],
    "end_to_end": [{"name": "pencil_p95_ms", "unit": "ms", "better": "lower", "bound": 0.25,
                    "source": "host_clock", "workloads": [EEG]}],
    "per_layer": [
        {"name": name, "unit": unit, "better": better, "source": source, "layer": layer,
         "moves": "pencil_p95_ms", "workloads": [EEG]}
        for name, unit, better, source, layer in [
            ("front_ms.pencil", "ms", "lower", "program_span", "service front"),
            ("bucket_fill", "req/unit", "higher", "program_counter", "batching"),
            ("late_ms_p95", "ms", "lower", "host_clock", "load generator"),
            ("device_idle.pencil", "%", "lower", "device_trace", "device"),
        ]
    ],
}


def copy_benchmark(dest: Path) -> Path:
    """``BENCHMARK.json`` and ``perfbench/`` (without its tests) into ``dest``."""
    dest = Path(dest)
    shutil.copy(harness.ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(harness.HERE, dest / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "testdata"))
    return dest


def add_entries(root: Path, entries: dict) -> None:
    """Append ``entries`` (lists by ``BENCHMARK.json`` key) to ``root``'s benchmark."""
    path = Path(root) / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    for key, items in entries.items():
        bench[key] += items
    path.write_text(json.dumps(bench))


_EEG_ROOT = []


def eeg_root() -> Path:
    """A copy of the benchmark with the pencil cell in it, made once per process."""
    if not _EEG_ROOT:
        root = Path(tempfile.mkdtemp(prefix="perfbench-eeg-"))
        atexit.register(shutil.rmtree, root, True)
        add_entries(copy_benchmark(root), EEG_ENTRIES)
        _EEG_ROOT.append(root)
    return _EEG_ROOT[0]


def run(cell: str, seed: int = 2**31 + 77, seconds: float = 1.0, trace: bool = False,
        root=None, traffic=None, overrides=None):
    """One rehearsal run of ``cell``; returns ``(run, result line)``."""
    if root is None:
        root = eeg_root() if cell == EEG else harness.ROOT
    r = harness.Run(cell, seed, seconds, trace=trace, root=root,
                    overrides=dict(OVERRIDES.get(cell, {}), **(overrides or {})),
                    traffic_overrides=dict(TRAFFIC.get(cell, {}), **(traffic or {})), grace_s=5.0)
    r.peaks = lambda: {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    line = harness.execute(r, harness.cpu_device())
    return r, line


def assert_well_formed(line: dict, run) -> None:
    """The contract's last line: keys, the cell's metrics with their units,
    every value a finite nonzero number, ``checks`` last."""
    keys = list(line)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert keys[-1] == "checks"
    assert set(keys) <= {"correct", "attempted", "failed", "metrics", "device", "breakdown", "checks"}
    json.loads(json.dumps(line, allow_nan=False))
    want = {m["name"]: m["unit"] for m in run.metric_entries()}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    for v in line["metrics"].values():
        assert math.isfinite(v["value"]) and v["value"] != 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    if run.trace:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
