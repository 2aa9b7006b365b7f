"""Readings that the check's limits are set from, for many seeds in one process.

    python3 perfbench/control.py --workload nyx-256-compress --seconds 10 \
        --seeds 11,12,13,14,15,16,17,18,19,20,21,22

For each seed it runs the cell as ``run.py`` does (set-up, a window of
``--seconds``, the check) and prints one JSON line with every compared
number twice: as the program gives it (its largest value over the seeds is
the lower reading of the limit) and as the control gives it (the float64
reference's bound resolution carried in bfloat16 in the blob's place; its
smallest value is the upper reading).  The compiled programs stay in memory
from one seed to the next, so only the first seed pays the warm-up's
compiles.  The benchmark's own runs never run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from perfbench import harness

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}[args.workload]
    device = harness.require_device(int(chips))
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run = harness.Run(args.workload, seed, args.seconds, t0=t0)
        run.device = device
        run.setup()
        try:
            run.window = run.run_window()
        finally:
            run.close()
        checks = run.check(control=True)
        print(json.dumps({
            "seed": seed,
            "requests": len(run.window.requests),
            "checked": run.checked,
            "compiles_in_window": run.window.compiles,
            "program": {k: c["value"] for k, c in checks.items()},
            "control": {k: c["control"] for k, c in checks.items() if "control" in c},
            "limit": {k: c["limit"] for k, c in checks.items()},
            "seconds": time.perf_counter() - t0,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
