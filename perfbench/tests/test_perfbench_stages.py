"""The program's stage spans read from a trace (perfbench/stages.py): time
clipped to the window and summed over threads, polish round trips counted by
overlap, and idle gaps named by the stage spans open in them, on the
synthetic TPU layout and the recorded CPU trace of test_perfbench_trace.py;
then traced CPU rehearsals through ``harness.Run``: every stage reading
gives a number and the breakdown's idle gaps carry stage names; a metric
whose source the program never writes is left out; one whose source holds
nothing in the window, and an end-to-end metric that reads None, are errors."""

import math
import time
from types import SimpleNamespace as NS

import jax
import pytest

from perfbench import harness, stages, trace_reduce
from perfbench.tests import tiny
from perfbench.tests.test_perfbench_trace import TRACE, _event


def _layout(host_lines):
    """The synthetic TPU layout: a window [5, 400] and device ops at
    [10, 70], [160, 240] and [310, 400], so the idle gaps are [70, 160]
    (middle 115) and [240, 310] (middle 275); ``host_lines`` are the
    program's threads, each a list of events."""
    modules = [_event("jit_a(7)", 0, 100), _event("jit_b(9)", 150, 100),
               _event("jit_a(7)", 300, 200)]
    ops = [_event("f1", 10, 40), _event("f2", 40, 30), _event("g", 160, 80),
           _event("f1", 310, 150)]
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=modules),
                                             NS(name="XLA Ops", events=ops)])
    main = NS(name="main", events=[_event("bench.window", 5, 395), _event("bench.step", 90, 60)])
    lines = [main] + [NS(name="python", events=evs) for evs in host_lines]
    return NS(planes=[NS(name="/host:CPU", lines=lines), device])


SCHEDULER = [_event("ffcz.front", 0, 60, uid="r1", kind="field", n=1),
             _event("ffcz.plan", 0, 20), _event("ffcz.base", 20, 30),
             _event("ffcz.wait", 100, 80, uid="r0")]
WORKER = [_event("ffcz.back", 50, 340, uid="r0", kind="field", n=1),
          _event("ffcz.polish", 60, 300),
          _event("ffcz.polish.round", 80, 50), _event("ffcz.polish.round", 250, 40),
          _event("ffcz.polish.round", 395, 20)]


def test_stage_time_is_clipped_to_the_window_and_summed_over_threads():
    # the same stage on both threads: [0, 60] clips to 55, [350, 450] to 50
    pd = _layout([[_event("ffcz.base", 0, 60)], [_event("ffcz.base", 350, 100)]])
    p = stages.ProgramTrace.from_profile(pd)
    assert (p.lo, p.hi) == (5, 400)
    assert p.stage_ns("ffcz.base") == 55 + 50
    assert p.stage_ns("ffcz.plan") is None  # no such span: nothing to read
    run = NS(program=p, cfg={"kind": "field"}, window=NS(counters={"completed": 3}))
    assert stages.ms_per_field(run, "ffcz.base") == pytest.approx(105e-6 / 3)
    untraced = NS(program=None, cfg={"kind": "field"}, window=run.window)
    assert stages.ms_per_field(untraced, "ffcz.base") is None
    # written, but only outside the window: written, and nothing to read
    outside = stages.ProgramTrace.from_profile(_layout([[_event("ffcz.plan", 400, 30)]]))
    assert outside.writes("ffcz.plan") and not outside.writes("ffcz.base")
    assert outside.stage_ns("ffcz.plan") is None


def test_polish_rounds_are_counted_by_overlap():
    # the third round starts inside the window and ends past it: counted
    p = stages.ProgramTrace.from_profile(_layout([SCHEDULER, WORKER]))
    assert p.rounds_per_polish() == 3.0
    late = stages.ProgramTrace.from_profile(
        _layout([[_event("ffcz.polish", 390, 30), _event("ffcz.polish.round", 400, 5)]]))
    assert late.rounds_per_polish() == 0.0  # the round starts as the window ends
    assert stages.ProgramTrace.from_profile(_layout([])).rounds_per_polish() is None


def _named_gaps(pd):
    return trace_reduce.reduce(pd, name_gap=stages.ProgramTrace.from_profile(pd).name_gap).gaps


def test_gap_is_named_by_the_stage_open_on_each_thread():
    gaps = _named_gaps(_layout([SCHEDULER, WORKER]))
    # [70, 160], middle 115: the scheduler waits, the worker polishes (in a
    # round, which is not a stage and does not name it)
    assert gaps[0] == ("bench.step:ffcz.polish+ffcz.wait", pytest.approx(90e-9))
    # [240, 310], middle 275: no bench span; the worker is in a polish round
    assert gaps[1] == ("no bench span:ffcz.polish", pytest.approx(70e-9))


def test_rounds_alone_do_not_name_a_gap():
    spans = [stages.Span("ffcz.polish.round", ("/host:CPU", 1), 100, 130)]
    assert stages.name_gap("bench.step", spans, (70, 160)) == "bench.step"
    back = spans + [stages.Span("ffcz.back", ("/host:CPU", 1), 0, 400)]
    assert stages.name_gap("bench.step", back, (70, 160)) == "bench.step:ffcz.back"


def test_a_gap_with_no_program_span_keeps_its_name():
    pd = _layout([])
    assert _named_gaps(pd) == trace_reduce.reduce(pd).gaps
    recorded = trace_reduce.load(str(TRACE))
    assert stages.program_spans(recorded) == []
    assert _named_gaps(recorded) == trace_reduce.reduce(recorded).gaps


#: every reader of the program's stage spans and waits; dispatch_ms.field
#: and handoff_ms.field are not listed in BENCHMARK.json
STAGE_METRICS = ("plan_ms.field", "base_ms.field", "dispatch_ms.field", "fence_ms.field",
                 "fetch_ms.field", "polish_ms.field", "polish_iters", "queue_ms.field",
                 "handoff_ms.field")


def _per_layer(name, unit="ms"):
    return {"name": name, "unit": unit, "better": "lower", "source": "program_span",
            "layer": "a test", "moves": "field_GBps", "workloads": [tiny.NYX]}


def _benchmark_with(root, per_layer=(), end_to_end=(), readers=None):
    """A copy of the benchmark in ``root`` with entries and readers added."""
    tiny.copy_benchmark(root)
    for name, text in (readers or {}).items():
        (root / "perfbench" / "metrics" / f"{name}.py").write_text(text)
    tiny.add_entries(root, {"per_layer": list(per_layer), "end_to_end": list(end_to_end)})
    return root


def test_traced_rehearsal_reads_every_stage(tmp_path):
    """The whole-field cell at a tiny size: every stage reading is a finite
    number in the traced line that ``harness.execute`` prints."""
    listed = {m["name"] for m in harness.load_json(harness.ROOT / "BENCHMARK.json")["per_layer"]}
    root = _benchmark_with(tmp_path, [_per_layer(n) for n in STAGE_METRICS if n not in listed])
    run, line = tiny.run(tiny.NYX, seed=2**31 + 91, trace=True, root=root)
    assert line["correct"] is True and list(line)[-1] == "checks"
    assert set(STAGE_METRICS) <= set(line["metrics"])
    for name in STAGE_METRICS:
        value = line["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, name
    assert line["metrics"]["queue_ms.field"]["value"] > 0
    assert run.window.compiles == 0


def test_idle_gaps_carry_stage_names():
    """The repo's own benchmark, traced: the breakdown's idle gaps are named
    by the stage spans open in them, after the ``bench.*`` span."""
    run, line = tiny.run(tiny.NYX, seed=2**31 + 93, trace=True)
    tiny.assert_well_formed(line, run)
    gaps = [name for name, _s in line["breakdown"]["idle_gaps"]]
    assert any(":ffcz." in name for name in gaps), gaps
    assert all(name.split(":")[0].startswith(("bench.", "no bench span")) for name in gaps)


NEVER_WRITTEN = {
    "never_span": ("span", "ffcz.never"),
    "never_counter": ("counter", "never"),
    "never_timer": ("timer", "never_s"),
    "never_stat": ("stat", "never_s"),
}


def test_a_source_the_program_never_writes_is_left_out(tmp_path, capfd):
    readers = {name: f"SOURCE = {source!r}\n\n\ndef read(run):\n    return None\n"
               for name, source in NEVER_WRITTEN.items()}
    root = _benchmark_with(tmp_path, [_per_layer(n) for n in NEVER_WRITTEN], readers=readers)
    run, line = tiny.run(tiny.NYX, seed=2**31 + 95, trace=True, root=root)
    err = capfd.readouterr().err
    assert line["correct"] is True
    assert set(line["metrics"]) == {m["name"] for m in run.metric_entries()} - set(NEVER_WRITTEN)
    for name, (kind, src) in NEVER_WRITTEN.items():
        assert f"metric {name} left out: the program writes no {kind} {src!r}" in err
    # what the program does write counts as written
    for source in [("span", "ffcz.plan"), ("counter", "completed"), ("timer", "front_s"),
                   ("stat", "queue_s")]:
        assert run.writes(source), source


LATE_SPAN = "ffcz.late"


def test_a_source_written_outside_the_window_raises(tmp_path, monkeypatch):
    """The program writes the span, but only after the window closed: the
    reader finds nothing in the window, and the run is an error."""
    stop = harness.Run._trace_stop

    def late_then_stop(self, log_dir):
        with jax.profiler.TraceAnnotation(LATE_SPAN):
            time.sleep(0.002)
        stop(self, log_dir)

    monkeypatch.setattr(harness.Run, "_trace_stop", late_then_stop)
    reader = (f"from perfbench import stages\n\nSOURCE = ('span', {LATE_SPAN!r})\n\n\n"
              "def read(run):\n    return stages.ms_per_field(run, SOURCE[1])\n")
    root = _benchmark_with(tmp_path, [_per_layer("late_ms.field")],
                           readers={"late_ms.field": reader})
    with pytest.raises(RuntimeError, match="metric late_ms.field has nothing to read"):
        tiny.run(tiny.NYX, seed=2**31 + 97, trace=True, root=root)


def test_an_end_to_end_metric_that_reads_none_raises(tmp_path):
    """The absent-source rule is for per-layer metrics of a traced run only."""
    entry = {"name": "never_e2e", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock", "workloads": [tiny.NYX]}
    root = _benchmark_with(tmp_path, end_to_end=[entry], readers={
        "never_e2e": "SOURCE = ('span', 'ffcz.never')\n\n\ndef read(run):\n    return None\n"})
    with pytest.raises(RuntimeError, match="metric never_e2e has nothing to read"):
        tiny.run(tiny.NYX, seed=2**31 + 99, trace=False, root=root)
