"""The program's stage spans read from a trace (perfbench/stages.py): time
clipped to the window and summed over threads, polish round trips counted by
overlap, and idle gaps named by the stage spans open in them, on the
synthetic TPU layout and the recorded CPU trace of test_perfbench_trace.py;
then a traced CPU rehearsal in which every stage reading gives a number."""

import math
from types import SimpleNamespace as NS

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
    assert stages.ms_per_field(NS(cfg={"kind": "field"}, window=run.window), "ffcz.base") is None


def test_polish_rounds_are_counted_by_overlap():
    # the third round starts inside the window and ends past it: counted
    p = stages.ProgramTrace.from_profile(_layout([SCHEDULER, WORKER]))
    assert p.rounds_per_polish() == 3.0
    late = stages.ProgramTrace.from_profile(
        _layout([[_event("ffcz.polish", 390, 30), _event("ffcz.polish.round", 400, 5)]]))
    assert late.rounds_per_polish() == 0.0  # the round starts as the window ends
    assert stages.ProgramTrace.from_profile(_layout([])).rounds_per_polish() is None


def test_gap_is_named_by_the_stage_open_on_each_thread():
    p = stages.ProgramTrace.from_profile(_layout([SCHEDULER, WORKER]))
    # [70, 160], middle 115: the scheduler waits, the worker polishes (in a
    # round, which is not a stage and does not name it)
    assert p.gaps[0] == ("bench.step:ffcz.polish+ffcz.wait", pytest.approx(90e-9))
    # [240, 310], middle 275: no bench span; the worker is in a polish round
    assert p.gaps[1] == ("no bench span:ffcz.polish", pytest.approx(70e-9))


def test_rounds_alone_do_not_name_a_gap():
    spans = [stages.Span("ffcz.polish.round", ("/host:CPU", 1), 100, 130, {})]
    assert stages.name_gap("bench.step", spans, (70, 160)) == "bench.step"
    back = spans + [stages.Span("ffcz.back", ("/host:CPU", 1), 0, 400, {})]
    assert stages.name_gap("bench.step", back, (70, 160)) == "bench.step:ffcz.back"


def test_a_gap_with_no_program_span_keeps_its_name():
    pd = _layout([])
    assert stages.ProgramTrace.from_profile(pd).gaps == trace_reduce.reduce(pd).gaps
    recorded = trace_reduce.load(str(TRACE))
    assert stages.program_spans(recorded) == []
    assert stages.named_gaps(recorded, []) == trace_reduce.reduce(recorded).gaps


def test_traced_rehearsal_reads_every_stage():
    """The whole-field cell at a tiny size: every stage reading is a finite
    number, beside the cell's usual traced line."""
    run = stages.StagedRun(tiny.NYX, 2**31 + 91, 1.0, trace=True,
                           overrides=tiny.OVERRIDES[tiny.NYX], grace_s=5.0)
    run.peaks = lambda: {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    line = stages.staged_line(run, harness.cpu_device())
    assert line["correct"] is True and list(line)[-1] == "checks"
    assert set(line["stages"]) == set(stages.READINGS)
    for name, value in line["stages"].items():
        assert value is not None and math.isfinite(value) and value >= 0, name
    assert line["stages"]["queue_ms.field"] > 0
    assert set(line["end_to_end"]) == {"field_GBps", "ratio", "setup_s"}
    assert line["compiles_in_window"] == 0
    assert any(":ffcz." in name for name, _s in line["named_gaps"])
