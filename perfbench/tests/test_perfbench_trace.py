"""The trace reduction on a small recorded CPU trace and on a synthetic TPU
layout, the peak table, and the POCS work count, against hand-computed
values."""

import math
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from perfbench import peaks, trace_reduce, workcount

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "cpu_trace.xplane.pb"

# Events of the recorded trace (ns), read by hand from the file: the window
# span, one host span inside it, and nine operations of two programs, none
# overlapping another.
WINDOW = (2181840, 2181840 + 4275787)
OPS = {
    "jit_spectrum": [(2577096, 82930), (2661238, 16742), (2679179, 5861), (2685427, 1708),
                     (6370471, 45850), (6416936, 5241), (6422894, 5021), (6428295, 1372)],
    "jit_clip": [(6237275, 13202)],
}


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(trace_reduce.load(str(TRACE)))


def test_busy_union_and_idle_share(summary):
    busy = sum(d for ops in OPS.values() for _, d in ops)
    assert busy == 177927
    assert summary.window_s == pytest.approx(4275787e-9, rel=1e-12)
    assert summary.busy_s == pytest.approx(177927e-9, rel=1e-12)
    assert summary.idle_share == pytest.approx(1 - 177927 / 4275787, rel=1e-12)


def test_per_module_time_and_calls(summary):
    assert summary.module_s == pytest.approx({"jit_spectrum": 164725e-9, "jit_clip": 13202e-9})
    assert summary.module_calls == {"jit_spectrum": 2, "jit_clip": 1}
    assert summary.module_whole_s == pytest.approx(summary.module_s)
    assert trace_reduce.module_seconds(summary, "spectrum") == pytest.approx(164725e-9)
    assert trace_reduce.module_seconds(summary, "alternating_projection") is None


def test_gaps_are_named_by_the_open_host_span(summary):
    # reduce ends 2687135, the clip starts 6237275: its middle lies in bench.step
    assert summary.gaps[0] == ("bench.step", pytest.approx(3550140e-9))
    # window start -> first op, and clip end -> second fft: no span open
    assert summary.gaps[1] == ("no bench span", pytest.approx(395256e-9))
    assert summary.gaps[2] == ("no bench span", pytest.approx(119994e-9))
    b = trace_reduce.breakdown(summary)
    assert b["device_ops"][0] == ["fft.0", pytest.approx(128780e-9)]
    assert len(b["idle_gaps"]) == 10


def _event(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats.items()))


def test_tpu_layout_attributes_ops_to_modules():
    """Ops without an ``hlo_module`` stat belong to the module execution that
    holds them; a module execution cut by the window is not a whole call."""
    modules = [_event("jit_a(7)", 0, 100), _event("jit_b(9)", 150, 100), _event("jit_a(7)", 300, 200)]
    ops = [_event("f1", 10, 40), _event("f2", 40, 30), _event("g", 160, 80),
           _event("f1", 310, 150)]
    plane = NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=modules),
                                            NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[_event("bench.window", 5, 395),
                                                                  _event("bench.step", 90, 60)])])
    s = trace_reduce.reduce(NS(planes=[host, plane]))
    # window [5, 400]: ops union [10, 70] + [160, 240] + [310, 400] = 60 + 80 + 90
    assert s.window_s == pytest.approx(395e-9)
    assert s.busy_s == pytest.approx(230e-9)
    assert s.module_s == pytest.approx({"jit_a": 150e-9, "jit_b": 80e-9})
    assert s.module_calls == {"jit_a": 1, "jit_b": 1}  # jit_a at 300 runs past the window
    assert s.module_whole_s == pytest.approx({"jit_a": 60e-9, "jit_b": 80e-9})
    assert s.gaps[0] == ("bench.step", pytest.approx(90e-9))  # [70, 160], middle 115


def test_union_and_gaps_by_hand():
    assert trace_reduce.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 11) == [(1, 4), (5, 11)]
    assert trace_reduce.gaps([(1, 4), (5, 11)], 0, 14) == [(0, 1), (4, 5), (11, 14)]


def test_peak_table_refuses_an_unknown_device():
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.lookup("TPU v9 imaginary")


@pytest.mark.parametrize("shape", [(8, 8, 8), (256, 256, 256), (64, 512), (16, 10)])
def test_pocs_work_count_by_hand(shape):
    n = math.prod(shape)
    half = math.prod(shape[:-1]) * (shape[-1] // 2 + 1)
    w = workcount.pocs_iteration(shape)
    assert w["flops"] == pytest.approx(5 * n * math.log2(n) + 6 * half + 2 * n)
    assert w["bytes"] == 16 * n


def test_pocs_work_count_at_256_cubed_is_bytes_bound():
    w = workcount.pocs_iteration((256, 256, 256))
    roof = workcount.roofline_seconds(w, peaks.lookup("TPU v5 lite"))
    assert roof["bound"] == "bytes"
    assert roof["seconds"] == pytest.approx(16 * 256**3 / 819e9)
    with pytest.raises(ValueError):
        workcount.pocs_iteration(())


def test_loop_ops_count_their_self_time_under_a_short_name():
    modules = [_event("jit_loop(3)", 0, 300)]
    ops = [_event("%while.5 = (f32[4,8]{1,0}, s32[]) while(%t), body=%b", 10, 200),
           _event("%fusion.1 = f32[4,8]{1,0:T(8,128)} fusion(%p), kind=kLoop", 20, 50),
           _event("%fusion.2 = f32[4,8]{1,0:T(8,128)} fusion(%q), kind=kLoop", 100, 60),
           _event("%copy.3 = f32[4,8]{0,1} copy(%r)", 250, 20)]
    plane = NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=modules),
                                            NS(name="XLA Ops", events=ops)])
    s = trace_reduce.reduce(NS(planes=[plane]))
    assert s.op_s == pytest.approx({"%while.5 (f32[4,8]": 90e-9, "%fusion.1 f32[4,8]": 50e-9,
                                    "%fusion.2 f32[4,8]": 60e-9, "%copy.3 f32[4,8]": 20e-9})
    assert s.busy_s == pytest.approx(220e-9)  # [10, 210] + [250, 270]
