"""The program's own stage spans in a traced window, and a run that reads them.

The FFCz service and engine write host spans named ``ffcz.<stage>`` (one
dot), and ``ffcz.polish.round`` for each float64 round trip of the polish
(``src/repro/core/spans.py``; the list is in docs/serving.md).  They land in
the profiler's ``.xplane.pb`` on the device planes' clock, one line per host
thread.  From one trace this module reads:

* each stage's time inside the window ``bench.window``, summed over the
  threads (``metrics/<stage>_ms.field.py`` divide it by the fields completed
  in the window);
* the polish's round trips: rounds over polish spans, each counted when it
  overlaps the window;
* the longest idle gaps of the device, named as ``trace_reduce`` names them
  (the ``bench.*`` span open in the middle) and then, after ``:``, by the
  deepest stage span open in the middle on each host thread, deduplicated,
  sorted and joined with ``+``: ``bench.step:ffcz.polish+ffcz.wait``.  A gap
  with no stage span open keeps its plain name.

``harness.Run`` keeps only ``trace_reduce.Summary`` and deletes the trace, so
these readings come from :class:`StagedRun`, which keeps them too
(``perfbench/run_stages.py`` runs one cell so and prints them).  On a program
that writes no ``ffcz.*`` span every stage reading is None.
"""

import dataclasses
import re
import shutil
from typing import Dict, List, Optional, Tuple

from perfbench import harness, trace_reduce

PREFIX = "ffcz."
STAGE = re.compile(r"^ffcz\.[A-Za-z_]+$")  # one dot: not ffcz.polish.round
ROUND, POLISH = "ffcz.polish.round", "ffcz.polish"

#: the per-layer metrics that read this module, each a file in metrics/
READINGS = ("plan_ms.field", "base_ms.field", "dispatch_ms.field", "fence_ms.field",
            "fetch_ms.field", "polish_ms.field", "polish_iters", "queue_ms.field",
            "handoff_ms.field")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    line: Tuple[str, int]  # (plane, line index): one line per host thread
    start: int  # ns
    end: int  # ns
    stats: Dict[str, object]


def program_spans(pd, prefix: str = PREFIX) -> List[Span]:
    """Every host span whose name starts with ``prefix``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefix):
                    s = int(e.start_ns)
                    out.append(Span(e.name, (plane.name, i), s, s + int(e.duration_ns),
                                    {k: v for k, v in e.stats}))
    return out


def name_gap(plain: str, spans: List[Span], gap: Tuple[int, int]) -> str:
    """``plain``, then ``:`` and the deepest stage span open at the gap's
    middle on each thread (deduplicated, sorted, joined with ``+``)."""
    mid = (gap[0] + gap[1]) // 2
    deepest: Dict[Tuple[str, int], Tuple[int, str]] = {}
    for sp in spans:
        if STAGE.match(sp.name) and sp.start <= mid < sp.end:
            cur = deepest.get(sp.line)
            if cur is None or sp.end - sp.start < cur[0]:
                deepest[sp.line] = (sp.end - sp.start, sp.name)
    names = sorted({name for _d, name in deepest.values()})
    return f"{plain}:{'+'.join(names)}" if names else plain


def window(pd) -> Tuple[int, int]:
    """The traced window as ``trace_reduce.reduce`` takes it (ns)."""
    spans = trace_reduce.host_spans(pd)
    windows = [(s, e) for name, s, e in spans if name == trace_reduce.WINDOW_SPAN]
    if windows:
        return windows[0]
    ops = trace_reduce.device_ops(pd)
    if not ops:
        raise ValueError("the trace has no window span and no device operation")
    return min(o.start for o in ops), max(o.end for o in ops)


def named_gaps(pd, spans: List[Span], top: int = 10) -> List[Tuple[str, float]]:
    """The device's longest idle gaps in the window, as in
    ``trace_reduce.reduce``, each named with the stage spans open in it."""
    lo, hi = window(pd)
    ops = trace_reduce.device_ops(pd)
    chips = sorted({o.chip for o in ops})
    busy = trace_reduce.union([(o.start, o.end) for o in ops if chips and o.chip == chips[0]],
                              lo, hi)
    idle = sorted(trace_reduce.gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    bench = trace_reduce.host_spans(pd)
    return [(name_gap(trace_reduce._name_gap(bench, g), spans, g), (g[1] - g[0]) * 1e-9)
            for g in idle]


@dataclasses.dataclass
class ProgramTrace:
    """What a traced window holds of the program's own spans."""

    spans: List[Span]
    lo: int
    hi: int
    gaps: List[Tuple[str, float]]

    @classmethod
    def from_profile(cls, pd, top: int = 10) -> "ProgramTrace":
        spans = program_spans(pd)
        lo, hi = window(pd)
        return cls(spans, lo, hi, named_gaps(pd, spans, top))

    def stage_ns(self, name: str) -> Optional[int]:
        """Time of the spans named ``name`` inside the window, summed over
        threads; None when the trace has no span of that name."""
        hits = [sp for sp in self.spans if sp.name == name]
        if not hits:
            return None
        return sum(max(0, min(sp.end, self.hi) - max(sp.start, self.lo)) for sp in hits)

    def rounds_per_polish(self) -> Optional[float]:
        """``ffcz.polish.round`` spans over ``ffcz.polish`` spans, each
        counted when it overlaps the window; None when no polish does."""

        def overlapping(name):
            return sum(sp.name == name and sp.start < self.hi and sp.end > self.lo
                       for sp in self.spans)

        polishes = overlapping(POLISH)
        return overlapping(ROUND) / polishes if polishes else None


def ms_per_field(run, name: str) -> Optional[float]:
    """Milliseconds of stage ``name`` in the traced window per field the
    service completed there; None without a field, a trace or the span."""
    program = getattr(run, "program", None)
    w = run.window
    if program is None or run.cfg["kind"] != "field" or not w.counters["completed"]:
        return None
    ns = program.stage_ns(name)
    return None if ns is None else ns * 1e-6 / w.counters["completed"]


def admitted_in_window(run) -> list:
    """The window's completed fields that were submitted after it opened: in
    a closed loop the others were admitted during set-up, so their waits
    hold the set-up's compiles."""
    w = run.window
    return [r for r in w.completed if r.submit is not None and r.submit >= w.start]


def mean_wait_ms(run, field: str) -> Optional[float]:
    """Mean ``RequestStats.<field>`` of :func:`admitted_in_window`, in ms;
    None where the program's stats have no such field."""
    done = admitted_in_window(run)
    if run.cfg["kind"] != "field" or not done:
        return None
    vals = [getattr(r.resp.stats, field, None) for r in done]
    if any(v is None for v in vals):
        return None
    return sum(vals) / len(vals) * 1e3


class StagedRun(harness.Run):
    """A ``harness.Run`` whose traced window also keeps a :class:`ProgramTrace`."""

    program: Optional[ProgramTrace] = None

    def _trace_stop(self, log_dir) -> None:
        if log_dir is None:
            return
        import jax

        jax.profiler.stop_trace()
        try:
            pd = trace_reduce.load(trace_reduce.find_xplane(log_dir))
            self.summary = trace_reduce.reduce(pd)
            self.program = ProgramTrace.from_profile(pd)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)


def _read(run, name: str) -> Optional[float]:
    value = harness.load_module(harness.HERE / "metrics" / f"{name}.py").read(run)
    return None if value is None else float(value)


def readings(run) -> Dict[str, Optional[float]]:
    """Every metric of ``READINGS`` through its reader in ``metrics/``."""
    return {name: _read(run, name) for name in READINGS}


def staged_line(run: StagedRun, device: dict) -> dict:
    """``harness.execute``'s line (per-layer metrics, breakdown, checks) with
    the end-to-end metrics, the stage readings, the named gaps and each
    request's waits added."""
    line = harness.execute(run, device)
    end_to_end = {m["name"]: _read(run, m["name"]) for m in run.bench["end_to_end"]
                  if "workloads" not in m or run.cell["name"] in m["workloads"]}
    waits = [[r.uid, r.submit - run.window.start, getattr(r.resp.stats, "queue_s", None),
              getattr(r.resp.stats, "handoff_s", None)] for r in run.window.completed]
    extra = {"end_to_end": end_to_end, "stages": readings(run),
             "named_gaps": [[k, v] for k, v in (run.program.gaps if run.program else [])],
             "waits": waits, "compiles_in_window": run.window.compiles}
    checks = line.pop("checks")
    line.update(extra)
    line["checks"] = checks
    return line
