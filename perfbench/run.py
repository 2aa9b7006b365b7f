"""Run one cell of the FFCz benchmark once on the chip.

    python3 perfbench/run.py --workload nyx-256-compress --seed 7 --seconds 30 --trace 0

The cell, its configuration, traffic and metrics are those ``BENCHMARK.json``
names (see ``perfbench/harness.py``).  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each compared number with its limit.
The same checks are the last lines of standard error.

Exits nonzero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True, help="a workload name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="seed of the data and the traffic")
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the window and report the per-layer metrics")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from perfbench import harness

    return harness.main(args, T0)


if __name__ == "__main__":
    sys.exit(main())
