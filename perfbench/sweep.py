"""Find an open-loop cell's knee: the highest rate served without a growing backlog.

    python3 perfbench/sweep.py --workload <open-loop cell> --seconds 51 \
        --rates 24,20,16,12

One process, one warm-up, then one window of ``--seconds`` per offered rate
(the queue is drained between rates).  Per rate it prints one JSON line:
the rate offered and completed, the median and 95th percentile latency from
the due time, the generator's lateness, and ``growth``, the median latency
of the window's last fifth over its first fifth.  A backlog that grows all
through the window shows as growth well above 1 and a completed rate below
the offered one.  The knee found this way is written into the traffic file
as a number; the benchmark's own runs never search for it.
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated offered rates, per second")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from perfbench import harness

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}[args.workload]
    device = harness.require_device(int(chips))
    harness.enable_compile_cache()
    run = harness.Run(args.workload, args.seed, args.seconds, grace_s=10.0)
    run.device = device
    run.setup()
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            run.traffic["rate_per_s"] = rate
            w = run.run_window()
            give_up = w.end + run.grace_s
            lat = np.array([(r.done if r.done is not None else give_up) - r.due
                            for r in w.requests])
            late = np.array([r.submit - r.due for r in w.requests if r.submit is not None])
            fifth = max(1, len(lat) // 5)
            print(json.dumps({
                "rate_per_s": rate,
                "completed_per_s": sum(r.done is not None and r.done <= w.end
                                       for r in w.requests) / w.seconds,
                "requests": len(w.requests),
                "not_clean": sum(not r.clean for r in w.requests),
                **{f"p{q}_ms": float(np.percentile(lat, q)) * 1e3 for q in (50, 90, 95, 99)},
                "max_ms": float(lat.max()) * 1e3,
                "late_p95_ms": float(np.percentile(late, 95)) * 1e3 if late.size else None,
                "growth": float(np.median(lat[-fifth:]) / np.median(lat[:fifth])),
                "bucket_fill": w.counters["completed"] / max(1, w.units),
                "compiles_in_window": w.compiles,
            }), flush=True)
            run.svc.drain()
    finally:
        run.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
