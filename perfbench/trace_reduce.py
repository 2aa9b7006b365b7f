"""Reduce a JAX profiler trace (``.xplane.pb``) to device time.

* busy: the union of the intervals in which an operation ran on a device,
  inside the traced window;
* idle share: 1 - busy / window;
* per-module time: the union of the intervals of the operations of one
  compiled program (an XLA module such as ``jit__alternating_projection``);
* per-operation self time (a ``while`` op less the ops of its body) and the
  longest idle gaps, each named by the benchmark's own host span
  (``bench.*``) that was open in its middle.

On a TPU each chip is a plane ``/device:TPU:<i>``; its ``XLA Ops`` line holds
the operations and its ``XLA Modules`` line the program executions an
operation is attributed to when it carries no ``hlo_module`` stat.  On the
CPU backend (used to test this file) operations run on host threads and
carry ``hlo_op`` / ``hlo_module`` stats.  The window is the host span
``bench.window`` when the trace has one, else the extent of the operations.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"\(\d+\)$")

Interval = Tuple[int, int]


@dataclasses.dataclass(frozen=True)
class Op:
    chip: str
    module: str
    name: str
    start: int  # ns
    end: int  # ns
    run: object = None  # which execution of the module the operation belongs to


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over chips
    module_s: Dict[str, float]  # mean over chips
    op_s: Dict[str, float]  # summed durations, mean over chips
    module_calls: Dict[str, float]  # executions wholly inside the window, mean over chips
    module_whole_s: Dict[str, float]  # device time of those executions, mean over chips
    gaps: List[Tuple[str, float]]  # longest idle gaps, named by host span
    chips: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` a ``jax.profiler.trace(log_dir)`` wrote."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    return paths[0]


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def _module_name(name: str) -> str:
    return _SUFFIX.sub("", name)


def _op_name(name: str) -> str:
    """``%fusion.3 = f32[8,128]{1,0} fusion(...)`` -> ``%fusion.3 f32[8,128]``."""
    lhs, sep, rhs = name.partition(" = ")
    return f"{lhs} {rhs.split('{', 1)[0]}" if sep else name


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def device_ops(pd) -> List[Op]:
    """Every device operation in the trace (see the module docstring)."""
    ops: List[Op] = []
    devices = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        modules = sorted(
            (int(e.start_ns), int(e.start_ns + e.duration_ns), _module_name(e.name))
            for e in (lines["XLA Modules"].events if "XLA Modules" in lines else ())
        )
        starts = [m[0] for m in modules]
        for e in lines["XLA Ops"].events if "XLA Ops" in lines else ():
            start, end = int(e.start_ns), int(e.start_ns + e.duration_ns)
            st = _stats(e)
            parent = _containing(modules, starts, start)
            module = st.get("hlo_module", parent[2] if parent else "?")
            run = st.get("run_id", parent[0] if parent else None)
            ops.append(Op(plane.name, _module_name(str(module)), _op_name(e.name), start, end,
                          run))
    if devices:
        return ops
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                st = _stats(e)
                if "hlo_op" in st and e.duration_ns > 0:
                    start = int(e.start_ns)
                    ops.append(Op("cpu", _module_name(str(st.get("hlo_module", "?"))),
                                  str(st["hlo_op"]), start, start + int(e.duration_ns),
                                  st.get("run_id")))
    return ops


def _containing(modules, starts, t: int):
    """The ``(start, end, name)`` module execution holding time ``t``, or None."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i]
    return None


def host_spans(pd, prefix: str = SPAN_PREFIX) -> List[Tuple[str, int, int]]:
    """The benchmark's own host spans: ``(name, start_ns, end_ns)``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)))
    return out


def union(intervals: Iterable[Interval], lo: Optional[int] = None,
          hi: Optional[int] = None) -> List[Interval]:
    """Sorted disjoint union of ``intervals``, clipped to ``[lo, hi]``."""
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def length(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` outside the union ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _name_gap(spans, gap: Interval) -> str:
    mid = (gap[0] + gap[1]) // 2
    covering = [(e - s, name) for name, s, e in spans if s <= mid < e and name != WINDOW_SPAN]
    return min(covering)[1] if covering else "no bench span"


def reduce(pd, top: int = 10) -> Summary:
    """Busy time, idle share, per-module and per-op time, longest gaps."""
    ops = device_ops(pd)
    spans = host_spans(pd)
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    elif ops:
        lo, hi = min(o.start for o in ops), max(o.end for o in ops)
    else:
        raise ValueError("the trace has no window span and no device operation")
    chips = sorted({o.chip for o in ops}) or ["none"]
    by_chip = defaultdict(list)
    by_module = defaultdict(list)
    runs = defaultdict(list)
    for o in ops:
        by_chip[o.chip].append((o.start, o.end))
        by_module[(o.chip, o.module)].append((o.start, o.end))
        runs[(o.chip, o.module, o.run)].append((o.start, o.end))
    op_ns = _self_times(ops, lo, hi)
    calls: Dict[str, int] = defaultdict(int)
    whole_ns: Dict[str, int] = defaultdict(int)
    for (_chip, module, _run), iv in runs.items():
        if min(s for s, _ in iv) >= lo and max(e for _, e in iv) <= hi:
            calls[module] += 1
            whole_ns[module] += length(union(iv))
    n = len(chips)
    busy = {c: union(by_chip[c], lo, hi) for c in chips}
    module_ns: Dict[str, int] = defaultdict(int)
    for (_chip, module), iv in by_module.items():
        module_ns[module] += length(union(iv, lo, hi))
    idle = sorted(gaps(busy[chips[0]], lo, hi), key=lambda g: g[0] - g[1])[:top]
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(length(b) for b in busy.values()) / n * 1e-9,
        module_s={m: v / n * 1e-9 for m, v in module_ns.items()},
        op_s={k: v / n * 1e-9 for k, v in op_ns.items()},
        module_calls={m: v / n for m, v in calls.items()},
        module_whole_s={m: v / n * 1e-9 for m, v in whole_ns.items()},
        gaps=[(_name_gap(spans, g), (g[1] - g[0]) * 1e-9) for g in idle],
        chips=n,
    )


def _self_times(ops: Sequence[Op], lo: int, hi: int) -> Dict[str, int]:
    """Per op name, the time inside ``[lo, hi]`` not covered by the ops
    nested in it (a loop op holds the ops of its body)."""
    out: Dict[str, int] = defaultdict(int)
    by_chip = defaultdict(list)
    for o in ops:
        by_chip[o.chip].append(o)
    for chip_ops in by_chip.values():
        chip_ops.sort(key=lambda o: (o.start, -o.end))
        own = [max(0, min(o.end, hi) - max(o.start, lo)) for o in chip_ops]
        stack: List[int] = []
        for i, o in enumerate(chip_ops):
            while stack and chip_ops[stack[-1]].end <= o.start:
                stack.pop()
            if stack and o.end <= chip_ops[stack[-1]].end:
                own[stack[-1]] -= own[i]
            stack.append(i)
        for o, t in zip(chip_ops, own):
            if t > 0:
                out[o.name] += t
    return out


def module_seconds(summary: Summary, pattern: str) -> Optional[float]:
    """Device seconds of the modules whose name contains ``pattern``; None
    when no such module ran in the window."""
    hits = [v for m, v in summary.module_s.items() if pattern in m]
    return sum(hits) if hits else None


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: top device ops and idle gaps."""
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary.gaps[:top]]}
