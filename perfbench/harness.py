"""Run one benchmark cell once: set-up, a measured window, the check, one line.

Everything a cell is made of is found by name from ``BENCHMARK.json``:

* ``configs/<config>.json``   the deployment: sizes, bounds, service settings,
                              the check's sample size and limits; its data
                              generator is ``configs/<config>.py``
* ``kinds/<kind>.py``         how a request of the configuration's ``kind``
                              is made, submitted, warmed up and checked
* ``traffic/<traffic>.json``  the mix, read by the one generator ``loadgen``
* ``metrics/<metric>.py``     one reader per metric, ``read(run)`` -> value
                              or None when the cell has nothing to read

A closed loop's window opens at its first completion (which is also its
warm-up) and closes at the first completion at least ``--seconds`` later,
so it holds whole requests and starts at the same phase in every run.  An
open loop warms every shape, runs the mix for ``ramp_s`` seconds, then
counts every request due in the next ``--seconds`` seconds, timed from when
it was due, waiting up to a minute past the close for the last of them.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
GRACE_S = 60.0
_READINGS = ("E_gap", "Delta_gap", "spatial", "spectral")
COMPILE_EVENT = "/jax/core/compile/"


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path, name: Optional[str] = None):
    """Import a file by path (metric names hold dots, so not by module name)."""
    name = name or "perfbench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class CompileClock:
    """Counts and sums JAX's compile events (trace, lower, backend compile),
    and names what compiled since :meth:`watch` was called."""

    def __init__(self):
        self.count = 0
        self.backend = 0
        self.seconds = 0.0
        self.watched: Optional[List[str]] = None

    def __call__(self, event: str, duration: float, fun_name: str = "?", **_kw) -> None:
        if event.startswith(COMPILE_EVENT):
            self.count += 1
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.backend += 1
            if self.watched is not None:
                self.watched.append(f"{event.rsplit('/', 1)[-1]}:{fun_name}")

    def watch(self) -> None:
        self.watched = []


def require_device(chips: int) -> dict:
    """The device as JAX reports it; exits nonzero unless it is a TPU with
    at least ``chips`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX reports platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX reports {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def cpu_device() -> dict:
    """The CPU as JAX reports it (rehearsals on the CPU only)."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Request:
    index: int
    size: str
    uid: str
    due: Optional[float] = None  # open loop: when it was due (host clock)
    submit: Optional[float] = None
    done: Optional[float] = None
    resp: Any = None
    in_window: bool = False

    @property
    def clean(self) -> bool:
        r = self.resp
        return bool(r is not None and r.ok and not r.stats.rungs and r.stats.converged)


@dataclasses.dataclass
class Window:
    start: float
    end: float
    requests: List[Request]  # the window's requests
    units: int  # units of work step() retired inside the window
    timers: Dict[str, float]  # service stage clocks, change over the window
    counters: Dict[str, int]
    compiles: int  # JAX compile events inside the window (should be 0)
    backend_compiles: int

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def completed(self) -> List[Request]:
        return [r for r in self.requests if r.resp is not None and r.resp.ok]


class Run:
    """One cell, one seed.  ``overrides`` replaces configuration keys (the
    CPU rehearsals shrink sizes this way); ``device`` is the JAX device dict
    (from :func:`require_device`, or :func:`cpu_device` in rehearsals)."""

    def __init__(self, workload: str, seed: int, seconds: float, trace: bool = False,
                 root: Path = ROOT, overrides: Optional[dict] = None,
                 traffic_overrides: Optional[dict] = None, grace_s: float = GRACE_S,
                 t0: Optional[float] = None):
        self.t0 = time.perf_counter() if t0 is None else t0
        self.root, self.here = Path(root), Path(root) / "perfbench"
        if str(ROOT / "src") not in sys.path:
            sys.path.insert(0, str(ROOT / "src"))  # the program under test
        self.bench = load_json(self.root / "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
        self.cell = cells[workload]
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.cell["config"]]
        self.cfg = load_json(self.root / self.config_entry["file"])
        self.cfg.update(overrides or {})
        self.traffic = load_json(self.here / "traffic" / f"{self.cell['traffic']}.json")
        self.traffic.update(traffic_overrides or {})
        self.gen = load_module(self.root / self.config_entry["file"].replace(".json", ".py"))
        kind = load_module(self.here / "kinds" / f"{self.cfg['kind']}.py")
        self.seed, self.seconds, self.trace = int(seed), float(seconds), bool(trace)
        self.grace_s = grace_s
        self.kind = kind.Kind(self.cfg, self.gen, self.traffic, self.seed)
        self.clock = CompileClock()
        self.svc = None
        self.window: Optional[Window] = None
        self.summary = None  # trace_reduce.Summary of a traced run
        self.setup_s: Optional[float] = None
        self.memory_peak_bytes: Optional[int] = None
        self.device: dict = {}
        self._log_dir = self._span = self._snap = None
        self.checked = 0

    # -- set-up ------------------------------------------------------------

    def build_service(self):
        import argparse

        from repro.launch.serve_ffcz import add_fault_args, add_service_args, build_service

        ap = argparse.ArgumentParser()
        add_service_args(ap)
        add_fault_args(ap)
        svc_cfg = {k.replace("-", "_"): v for k, v in self.cfg.get("service", {}).items()}
        ap.set_defaults(seed=self.seed, **svc_cfg)
        return build_service(ap.parse_args([]))

    def setup(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.clock)
        self.kind.prepare()
        self.svc = self.build_service()
        self.kind.warm(self.svc)
        # the set-up's objects (data, compiled programs) live to the end:
        # keep the collector from walking them again inside the window
        gc.collect()
        gc.freeze()

    # -- the measured window -------------------------------------------------

    def _snapshot(self):
        return (dict(self.svc.timers), dict(self.svc.counters), self.clock.count,
                self.clock.backend)

    def _delta(self, snap) -> dict:
        timers, counters, count, backend = snap
        return {
            "timers": {k: v - timers[k] for k, v in self.svc.timers.items()},
            "counters": {k: v - counters[k] for k, v in self.svc.counters.items()},
            "compiles": self.clock.count - count,
            "backend_compiles": self.clock.backend - backend,
        }

    def _trace_start(self):
        if not self.trace:
            return None
        import jax

        log_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        return log_dir

    def _trace_stop(self, log_dir) -> None:
        if log_dir is None:
            return
        import jax

        from perfbench import trace_reduce

        jax.profiler.stop_trace()
        try:
            self.summary = trace_reduce.reduce(trace_reduce.load(trace_reduce.find_xplane(log_dir)))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)

    def run_window(self) -> Window:
        loop = self.traffic.get("loop")
        if loop == "closed":
            return self._closed()
        if loop == "open":
            return self._open()
        raise ValueError(f"unknown loop {loop!r}")

    def _open_window(self, now: float) -> None:
        import jax

        self.setup_s = now - self.t0
        self._log_dir = self._trace_start()
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self._snap = self._snapshot()
        self.clock.watch()

    def _close_window(self) -> dict:
        self._span.__exit__(None, None, None)
        delta = self._delta(self._snap)
        self._trace_stop(self._log_dir)
        return delta

    def _retire(self, live: Dict[str, Request]) -> List[Request]:
        import jax

        with jax.profiler.TraceAnnotation("bench.step"):
            resps = self.svc.step()
        now = time.perf_counter()
        out = []
        for resp in resps:
            req = live.pop(resp.uid)
            req.resp, req.done = resp, now
            out.append(req)
        return out

    def _closed(self) -> Window:
        in_flight = int(self.traffic["in_flight"])
        reqs: List[Request] = []
        live: Dict[str, Request] = {}
        start = None
        units = 0
        while True:
            while len(live) < in_flight:
                req = Request(index=len(reqs), size="1", uid=f"r{len(reqs)}")
                req.submit = time.perf_counter()
                self.kind.submit(self.svc, req)
                reqs.append(req)
                live[req.uid] = req
            done = self._retire(live)
            if not done:
                continue
            now = done[0].done
            if start is None:
                # the first completion: everything the window runs is warm
                start = now
                self._open_window(now)
                continue
            units += 1
            for req in done:
                req.in_window = True
            if now - start >= self.seconds:
                break
        delta = self._close_window()
        return Window(start=start, end=now, requests=[r for r in reqs if r.in_window],
                      units=units, **delta)

    def _open(self) -> Window:
        from perfbench import loadgen

        ramp = float(self.traffic.get("ramp_s", 2.0))
        sched = loadgen.schedule(self.traffic, self.seed, ramp + self.seconds + self.grace_s)
        reqs = [Request(index=i, size=a.size, uid=f"r{i}") for i, a in enumerate(sched)]
        live: Dict[str, Request] = {}
        base = time.perf_counter() + 0.05
        for r, a in zip(reqs, sched):
            r.due = base + a.due_s
        w0, w1 = base + ramp, base + ramp + self.seconds
        window = [r for r in reqs if w0 <= r.due < w1]
        for r in window:
            r.in_window = True
        waiting = len(window)
        nxt = units = 0
        phase = "ramp"  # -> "window" at w0 -> "tail" at w1
        delta = None
        while True:
            now = time.perf_counter()
            if phase == "ramp" and now >= w0:
                phase = "window"
                self._open_window(w0)  # the ramp is part of the set-up
            if phase == "window" and now >= w1:
                phase = "tail"
                delta = self._close_window()
            if phase == "tail" and (waiting == 0 or now >= w1 + self.grace_s):
                break
            while nxt < len(reqs) and reqs[nxt].due <= now:
                reqs[nxt].submit = time.perf_counter()
                self.kind.submit(self.svc, reqs[nxt])
                live[reqs[nxt].uid] = reqs[nxt]
                nxt += 1
            if live:
                done = self._retire(live)
                if done and phase == "window" and done[0].done <= w1:
                    units += 1
                waiting -= sum(r.in_window for r in done)
            elif nxt < len(reqs):
                wake = min(reqs[nxt].due, w0 if phase == "ramp" else w1 if phase == "window"
                           else reqs[nxt].due)
                time.sleep(max(0.0, wake - time.perf_counter()))
            else:
                break
        if delta is None:
            delta = self._close_window()
        # requests still owed past the grace never came: the check counts them
        late = self._delta(self._snap)
        delta["compiles"], delta["backend_compiles"] = late["compiles"], late["backend_compiles"]
        return Window(start=w0, end=w1, requests=window, units=units, **delta)

    # -- after the window ----------------------------------------------------

    def read_memory(self) -> None:
        import jax

        stats = [d.memory_stats() or {} for d in jax.devices()[: int(self.cell["chips"])]]
        peaks = [s.get("peak_bytes_in_use") for s in stats]
        self.memory_peak_bytes = max(p for p in peaks if p is not None) if any(
            p is not None for p in peaks) else None

    def close(self) -> None:
        if self.svc is not None:
            self.svc.close()

    def check(self, control: bool = False) -> Dict[str, dict]:
        """The compared numbers, each ``{"value", "limit"}`` (and with
        ``control`` the control's reading as ``"control"``): ``not_clean``
        counts the window's requests that failed, took a degradation rung,
        did not converge or never came; the others are the worst over a
        sample drawn from the seed, with the largest requests in it
        (``reference.compare``)."""
        reqs = self.window.requests
        not_clean = sum(not r.clean for r in reqs)
        done = [r for r in reqs if r.clean]
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 0xC4EC]))
        k = min(int(self.cfg["check_sample"]), len(done))
        sample = [done[i] for i in sorted(rng.choice(len(done), size=k, replace=False))]
        largest = self.kind.largest(done)
        if largest and not {r.uid for r in largest} & {r.uid for r in sample}:
            sample[-1] = largest[int(rng.integers(len(largest)))]
        self.checked = len(sample)
        sides = ("program", "control") if control else ("program",)
        worst = {side: dict.fromkeys(_READINGS, -np.inf) for side in sides}
        if not sample:
            worst = {side: dict.fromkeys(_READINGS, np.inf) for side in sides}
        for r in sample:
            try:
                got = self.kind.check(self.svc, r, control=control)
            except Exception as e:  # noqa: BLE001 - a blob that will not decode is wrong
                print(f"check {r.uid}: {type(e).__name__}: {e}", file=sys.stderr)
                got = {side: dict.fromkeys(_READINGS, np.inf) for side in sides}
            for side, vals in got.items():
                for name, v in vals.items():
                    worst[side][name] = max(worst[side][name], v)
        limits = self.cfg["limits"]
        out = {"not_clean": {"value": float(not_clean), "limit": limits["not_clean"]}}
        for name in _READINGS:
            out[name] = {"value": worst["program"][name], "limit": limits[name]}
            if control:
                out[name]["control"] = worst["control"][name]
        return out

    # -- metrics -------------------------------------------------------------

    def metric_entries(self) -> List[dict]:
        """The metrics this cell reports in this mode (``--trace``)."""
        key = "per_layer" if self.trace else "end_to_end"
        name = self.cell["name"]
        return [m for m in self.bench[key] if "workloads" not in m or name in m["workloads"]]

    def metrics(self) -> Dict[str, dict]:
        out = {}
        for m in self.metric_entries():
            reader = load_module(self.here / "metrics" / f"{m['name']}.py")
            value = reader.read(self)
            if value is None:
                raise RuntimeError(f"metric {m['name']} has nothing to read in {self.cell['name']}")
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def peaks(self) -> dict:
        from perfbench import peaks

        return peaks.lookup(self.device["kind"])


def execute(run: Run, device: dict) -> dict:
    """Set-up, window, memory, check and metrics of one run; the result line."""
    from perfbench import trace_reduce

    run.device = device
    run.setup()
    try:
        run.window = run.run_window()
        run.read_memory()
    finally:
        run.close()
    t_check = time.perf_counter()
    checks = run.check()
    t_check = time.perf_counter() - t_check
    metrics = run.metrics()
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    dev = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    line: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": len(run.window.requests),
        "failed": int(checks["not_clean"]["value"]),
        "metrics": metrics,
        "device": dev,
    }
    if run.summary is not None:
        dev["busy_s"] = run.summary.busy_s
        dev["window_s"] = run.summary.window_s
        line["breakdown"] = trace_reduce.breakdown(run.summary)
    # a number that could not be read (a blob that would not decode) is
    # printed as the largest float, which JSON can carry and no limit passes
    line["checks"] = {k: {f: (v if np.isfinite(v) else sys.float_info.max) for f, v in c.items()}
                      for k, c in checks.items()}
    w = run.window
    print(f"window {w.seconds:.3f}s, {len(w.requests)} requests, {w.units} units, "
          f"compiles in window {w.compiles} (backend {w.backend_compiles}), "
          f"setup {run.setup_s:.3f}s, {run.checked} responses checked in {t_check:.1f}s",
          file=sys.stderr)
    if w.compiles:
        print(f"compiled in the window: {sorted(set(run.clock.watched or []))}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return line


def main(args, t0: float) -> int:
    """The command line's body (``perfbench/run.py``)."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no workload {args.workload!r}; BENCHMARK.json has {sorted(cells)}")
    device = require_device(int(cells[args.workload]["chips"]))
    enable_compile_cache()
    run = Run(args.workload, args.seed, args.seconds, trace=bool(args.trace), t0=t0)
    run.device = device
    run.peaks()  # an unknown device kind is an error before any work
    line = execute(run, device)
    print(json.dumps(line), flush=True)
    return 0
