"""Run one cell of the FFCz benchmark once, traced, with the program's stage
spans read from the trace (``perfbench/stages.py``).

    python3 perfbench/run_stages.py --workload nyx-256-compress --seed 7 --seconds 51

The last line of standard output is ``perfbench/run.py --trace 1``'s JSON
object with five keys added before ``checks``: ``end_to_end`` (the cell's
end-to-end metrics, read from the same traced window), ``stages`` (the
readings of ``stages.READINGS``; null where the program writes no such span
or counter), ``named_gaps`` (the device's longest idle gaps, named with the
stage spans open in them), ``waits`` (per completed request: uid, submit
time from the window's opening, ``queue_s``, ``handoff_s``) and
``compiles_in_window``.

Exits nonzero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True, help="a workload name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="seed of the data and the traffic")
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from perfbench import harness, stages

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no workload {args.workload!r}; BENCHMARK.json has {sorted(cells)}")
    device = harness.require_device(int(cells[args.workload]["chips"]))
    harness.enable_compile_cache()
    run = stages.StagedRun(args.workload, args.seed, args.seconds, trace=True, t0=T0)
    run.device = device
    run.peaks()  # an unknown device kind is an error before any work
    print(json.dumps(stages.staged_line(run, device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
