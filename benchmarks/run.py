"""Benchmark harness: one module per paper table/figure (DESIGN.md §9).

Prints ``name,us_per_call,derived`` CSV summary lines plus each table's own
CSV; JSON artifacts land in benchmarks/results/.

  table2_ratio      Table II   compression ratios (native / trial-and-error / FFCz)
  fig6_ssnr         Fig. 6     SSNR vs bitrate
  fig7_throughput   Fig. 7     stage throughputs + pipeline bottleneck
  fig8_psnr         Fig. 8     spatial PSNR vs bitrate
  table3_iters      Table III  iterations / active edits vs Delta
  table4_kernels    Table IV   kernel-level breakdown
  fig10_pspec       Fig. 10    power-spectrum ribbon

``python -m benchmarks.run [--quick] [--only name]``
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sweeps (CI)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from benchmarks import (
        fig6_ssnr,
        fig7_throughput,
        fig8_psnr,
        fig10_pspec,
        table2_ratio,
        table3_iters,
        table4_kernels,
    )
    from benchmarks.common import print_csv

    modules = {
        "table2_ratio": table2_ratio,
        "fig6_ssnr": fig6_ssnr,
        "fig7_throughput": fig7_throughput,
        "fig8_psnr": fig8_psnr,
        "table3_iters": table3_iters,
        "table4_kernels": table4_kernels,
        "fig10_pspec": fig10_pspec,
    }
    if args.only:
        modules = {k: v for k, v in modules.items() if k in args.only.split(",")}

    print("name,us_per_call,derived")
    for name, mod in modules.items():
        t0 = time.perf_counter()
        rows = mod.run(quick=args.quick)
        dt = time.perf_counter() - t0
        print(f"{name},{dt * 1e6 / max(len(rows), 1):.1f},{len(rows)} rows")
        print_csv(rows, mod.COLUMNS)
        print()


if __name__ == "__main__":
    main()
