"""The program's own stage spans in a traced window.

The FFCz service and engine write host spans named ``ffcz.<stage>`` (one
dot), and ``ffcz.polish.round`` for each float64 round trip of the polish
(``src/repro/core/spans.py``; the list is in docs/serving.md).  They land in
the profiler's ``.xplane.pb`` on the device planes' clock, one line per host
thread.  ``harness.Run`` keeps a :class:`ProgramTrace` of every traced
window, read from the same load of the trace as ``trace_reduce.Summary``,
and from it the metrics read:

* each stage's time inside the window ``bench.window``, summed over the
  threads (``metrics/<stage>_ms.field.py`` divide it by the fields completed
  in the window);
* the polish's round trips: rounds over polish spans, each counted when it
  overlaps the window;
* the names of the device's longest idle gaps: ``trace_reduce``'s name (the
  ``bench.*`` span open in the middle), then ``:`` and the deepest stage
  span open in the middle on each host thread, deduplicated, sorted and
  joined with ``+``: ``bench.step:ffcz.polish+ffcz.wait``.  A gap with no
  stage span open keeps its plain name.

On a program that writes no ``ffcz.*`` span every stage reading is None; a
reader that names its span as ``SOURCE`` is then left out of a traced line
(``harness.Run.metrics``).
"""

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from perfbench import trace_reduce

PREFIX = "ffcz."
STAGE = re.compile(r"^ffcz\.[A-Za-z_]+$")  # one dot: not ffcz.polish.round
ROUND, POLISH = "ffcz.polish.round", "ffcz.polish"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    line: Tuple[str, int]  # (plane, line index): one line per host thread
    start: int  # ns
    end: int  # ns


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
                    out.append(Span(e.name, (plane.name, i), s, s + int(e.duration_ns)))
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


@dataclasses.dataclass
class ProgramTrace:
    """What a traced window holds of the program's own spans."""

    spans: List[Span]
    lo: int
    hi: int

    @classmethod
    def from_profile(cls, pd) -> "ProgramTrace":
        return cls(program_spans(pd), *trace_reduce.window(pd))

    def writes(self, name: str) -> bool:
        """Whether the trace holds a span named ``name``, in the window or not."""
        return any(sp.name == name for sp in self.spans)

    def name_gap(self, plain: str, gap: Tuple[int, int]) -> str:
        """:func:`name_gap` over this trace's spans (``trace_reduce.reduce``'s
        ``name_gap``)."""
        return name_gap(plain, self.spans, gap)

    def _overlapping(self, name: str) -> List[Span]:
        return [sp for sp in self.spans
                if sp.name == name and sp.start < self.hi and sp.end > self.lo]

    def stage_ns(self, name: str) -> Optional[int]:
        """Time of the spans named ``name`` inside the window, summed over
        threads; None when no span of that name overlaps the window."""
        hits = self._overlapping(name)
        if not hits:
            return None
        return sum(min(sp.end, self.hi) - max(sp.start, self.lo) for sp in hits)

    def rounds_per_polish(self) -> Optional[float]:
        """``ffcz.polish.round`` spans over ``ffcz.polish`` spans, each
        counted when it overlaps the window; None when no polish does."""
        polishes = len(self._overlapping(POLISH))
        return len(self._overlapping(ROUND)) / polishes if polishes else None


def ms_per_field(run, name: str) -> Optional[float]:
    """Milliseconds of stage ``name`` in the traced window per field the
    service completed there; None without a field, a trace or such a span
    in the window."""
    w = run.window
    if run.program is None or run.cfg["kind"] != "field" or not w.counters["completed"]:
        return None
    ns = run.program.stage_ns(name)
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
