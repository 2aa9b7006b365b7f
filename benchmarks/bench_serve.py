"""Serving throughput: pipelined (host ENCODE || device EXECUTE) vs serial.

Drives matched :class:`~repro.serving.ffcz_service.FFCzService` pairs —
``pipeline_depth=1`` (serial) vs ``pipeline_depth=2`` (pipelined) — over the
same seeded workload, two ways:

  saturating     the whole workload is queued up front (offered load is
                 infinite), so sustained throughput is requests / drain wall
                 time.  This is the ISSUE 7 acceptance measurement, recorded
                 as ``serve/pipelined-vs-serial``.
  offered-load   an open-loop arrival process at each ``--arrival-rates``
                 rate: requests are admitted on a clock while the driver
                 steps the service between arrivals, measuring achieved
                 throughput and p50/p99 latency under that offered load
                 (``serve/load-sweep`` rows).
  session-append per-frame incremental arrival through the durable session
                 path (open / append / finalize, one drain per frame — the
                 live-arrival model) vs one ``submit_stream`` over the same
                 frames, recorded as ``serve/session-append`` with the
                 wall-ratio ``speedup_session_vs_stream``; the finalize
                 container is asserted byte-identical to the stream path.

Workload mix, bounds, and fault probabilities reuse the
``launch/serve_ffcz.py`` flag groups, so any chaos configuration the service
CLI can serve, the bench can measure.  Pencil sizes are FIXED (2x block) so
bucket shapes repeat and jit compilation amortizes — the bench measures
steady-state serving, not compile time (a warmup drain precedes every timed
run for the same reason).

Rows merge into ``BENCH_pocs.json`` (replacing prior ``serve`` rows, keeping
every other bench's), with host/device busy fractions from the service's
stage timers and the host ``cpu_count`` — a single-core container cannot
overlap host and device work, and ``ci/check_bench.py`` gates the speedup
floor on that field.

Usage:  PYTHONPATH=src python benchmarks/bench_serve.py [--quick]
        PYTHONPATH=src python benchmarks/bench_serve.py --arrival-rates 5,20,80
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.core.temporal import TemporalConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve_ffcz import (
    add_fault_args,
    add_service_args,
    add_workload_args,
    build_service,
    field_config,
)


def _submit_one(svc, rng, args, cfg):
    """One request from the bench mix: fixed-size pencil or fixed-size field,
    so every bucket shape repeats and the jit cache stays warm."""
    if rng.random() < args.pencil_frac:
        x = rng.standard_normal(2 * args.block).astype(np.float32)
        return svc.submit_pencils(x, args.e_rel, args.delta_rel)
    edge = args.field_size
    return svc.submit_compress(rng.standard_normal((edge, edge)).astype(np.float32), cfg)


def _warmup(svc, args, rng_seed, n):
    """Replay the exact timed submission sequence once: bucket shapes depend
    on the pencil/field interleaving, and every distinct shape is a jit
    compilation — the timed run must only ever hit the warm cache."""
    cfg = field_config(args)
    rng = np.random.default_rng(rng_seed)
    for _ in range(n):
        _submit_one(svc, rng, args, cfg)
    svc.drain()


def _fractions(svc, wall):
    host = svc.timers["front_s"] + svc.timers["encode_s"] + svc.timers["decode_s"]
    return {
        "host_busy_frac": round(host / wall, 4),
        "device_wait_frac": round(svc.timers["execute_s"] / wall, 4),
    }


def _percentiles(lats):
    lats = np.asarray(lats, dtype=np.float64)
    return {
        "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3),
    }


def run_saturating(args, depth, n_requests):
    """Closed-loop: everything queued up front, drain, measure the wall."""
    svc = build_service(args, pipeline_depth=depth)
    _warmup(svc, args, rng_seed=args.seed + 1, n=n_requests)
    for k in svc.timers:
        svc.timers[k] = 0.0
    cfg = field_config(args)
    rng = np.random.default_rng(args.seed + 1)
    t0 = time.perf_counter()
    uids = [_submit_one(svc, rng, args, cfg) for _ in range(n_requests)]
    res = svc.drain()
    wall = time.perf_counter() - t0
    svc.close()
    assert set(res) == set(uids) and all(res[u].ok for u in uids), (
        "bench workload must fully complete; rejections mean the measurement "
        "is comparing different work"
    )
    lats = [res[u].stats.latency_s for u in uids]
    return wall, lats, _fractions(svc, wall)


def run_open_loop(args, depth, n_requests, rate_rps):
    """Open-loop arrival process at ``rate_rps``; the driver steps the
    service between arrivals so in-flight work progresses while the next
    request is still 'in the network'."""
    svc = build_service(args, pipeline_depth=depth)
    _warmup(svc, args, rng_seed=args.seed + 2, n=n_requests)
    cfg = field_config(args)
    rng = np.random.default_rng(args.seed + 2)
    interval = 1.0 / rate_rps
    res = {}
    t0 = time.perf_counter()
    uids = []
    for i in range(n_requests):
        due = t0 + i * interval
        while time.perf_counter() < due:
            if svc.pending:
                for r in svc.step():
                    res[r.uid] = r
            else:
                time.sleep(min(2e-4, max(0.0, due - time.perf_counter())))
        uids.append(_submit_one(svc, rng, args, cfg))
    res.update(svc.drain())
    wall = time.perf_counter() - t0
    svc.close()
    lats = [res[u].stats.latency_s for u in uids]
    return wall, lats


def _session_workload(args, n_frames, seed):
    """A coherent drifting field sequence, same shape discipline as the
    request mix: one fixed ``--field-size`` edge so jit stays warm."""
    rng = np.random.default_rng(seed)
    edge = args.field_size
    x = rng.standard_normal((edge, edge)).astype(np.float32)
    frames = [x]
    for _ in range(n_frames - 1):
        x = x + 0.05 * rng.standard_normal((edge, edge)).astype(np.float32)
        frames.append(x)
    return frames


def run_session_bench(args, n_frames):
    """serve/session-append: incremental per-frame arrival through the
    durable session path (open / append+drain per frame / finalize) vs one
    ``submit_stream`` over the same frames.  The session path prices
    admission, per-append journaling, and receipt bookkeeping on top of the
    same encode work, so the ratio sits near (below) 1.0 — the recorded
    ``speedup_session_vs_stream`` guards that overhead against collapse,
    it is not a speedup claim.  Appends drain one at a time because that is
    the live-arrival model the session exists for: the next frame does not
    exist until the previous ack."""
    svc = build_service(args, pipeline_depth=2)
    cfg = field_config(args)
    stream = TemporalConfig(mode="field", predictor="linear", keyframe_interval=4)
    frames = _session_workload(args, n_frames, args.seed + 3)

    def one_session():
        sid = svc.open_session(cfg, stream)
        lats = []
        for t, frame in enumerate(frames):
            t0 = time.perf_counter()
            uid = svc.submit_append(sid, t, frame)
            res = svc.drain()
            lats.append(time.perf_counter() - t0)
            assert res[uid].ok, f"bench append failed: {res[uid].error}"
        fin = svc.submit_finalize(sid)
        res = svc.drain()
        assert res[fin].ok
        return lats, res[fin].payload

    def one_stream():
        uid = svc.submit_stream(frames, cfg, stream)
        res = svc.drain()
        assert res[uid].ok
        return res[uid].payload

    one_session()  # warmup: first session compiles every bucket shape
    one_stream()
    for k in svc.timers:
        svc.timers[k] = 0.0

    t0 = time.perf_counter()
    lats, session_container = one_session()
    session_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    stream_container = one_stream()
    stream_wall = time.perf_counter() - t0
    svc.close()
    assert session_container == stream_container, (
        "session finalize must be byte-identical to submit_stream over the "
        "same frames (warm_start=False); the paths diverged"
    )
    return session_wall, stream_wall, lats


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="tiny run: emits every serve/* row kind for the CI "
                         "coverage check, measures nothing trustworthy")
    ap.add_argument("--out", default="BENCH_pocs.json",
                    help="merge serve rows into this bench record")
    ap.add_argument("--requests-per-run", type=int, default=0,
                    help="requests per timed run (0 = 12 quick / 96 full)")
    ap.add_argument("--arrival-rates", default="",
                    help="comma-separated offered loads (req/s) for the "
                         "open-loop sweep (default: one mid rate)")
    add_service_args(ap)
    add_workload_args(ap)
    add_fault_args(ap)
    args = ap.parse_args()
    enable_compile_cache()

    n = args.requests_per_run or (12 if args.quick else 96)
    rates = [float(r) for r in args.arrival_rates.split(",") if r] or [20.0]
    if args.quick:
        rates = rates[:1]
    cpu_count = os.cpu_count() or 1
    shape = [n, args.max_batch, args.block, args.field_size]
    common = {"cpu_count": cpu_count, "pencil_frac": args.pencil_frac}

    rows = []
    rps = {}
    for path, depth in (("serial", 1), ("pipelined", 2)):
        wall, lats, fracs = run_saturating(args, depth, n)
        rps[path] = n / wall
        rows.append({
            "bench": "serve", "path": path, "shape": shape,
            "pipeline_depth": depth, "wall_s": round(wall, 4),
            "rps": round(rps[path], 2), **_percentiles(lats), **fracs, **common,
        })
        print(f"saturating {path:>9} (depth {depth}): {rps[path]:7.2f} req/s  "
              f"host_busy={fracs['host_busy_frac']:.2f} "
              f"device_wait={fracs['device_wait_frac']:.2f}")

    speedup = rps["pipelined"] / rps["serial"]
    rows.append({
        "bench": "serve", "path": "pipelined-vs-serial", "shape": shape,
        "rps_serial": round(rps["serial"], 2),
        "rps_pipelined": round(rps["pipelined"], 2),
        "speedup_pipelined_vs_serial": round(speedup, 4), **common,
    })
    print(f"pipelined vs serial at saturating load: {speedup:.2f}x "
          f"({cpu_count} cpu core(s))")

    for rate in rates:
        wall, lats = run_open_loop(args, 2, n, rate)
        achieved = n / wall
        pct = _percentiles(lats)
        rows.append({
            "bench": "serve", "path": "load-sweep", "shape": shape,
            "pipeline_depth": 2, "offered_rps": rate,
            "achieved_rps": round(achieved, 2), **pct, **common,
        })
        print(f"open loop @ {rate:6.1f} req/s offered: {achieved:7.2f} achieved  "
              f"p50={pct['p50_ms']:.1f}ms p99={pct['p99_ms']:.1f}ms")

    n_frames = 4 if args.quick else 16
    session_wall, stream_wall, append_lats = run_session_bench(args, n_frames)
    # throughput ratio == wall ratio (same frame count both ways); near 1.0
    # means the session machinery (journal, receipts, admission) is cheap
    # next to the encode work, well below means it collapsed
    session_speedup = stream_wall / session_wall
    pct = _percentiles(append_lats)
    rows.append({
        "bench": "serve", "path": "session-append",
        "shape": [n_frames, args.field_size],
        "wall_session_s": round(session_wall, 4),
        "wall_stream_s": round(stream_wall, 4),
        "appends_per_s": round(n_frames / session_wall, 2),
        "speedup_session_vs_stream": round(session_speedup, 4),
        **pct, **common,
    })
    print(f"session append ({n_frames} frames): "
          f"{n_frames / session_wall:7.2f} appends/s  "
          f"vs stream {session_speedup:.2f}x  p99={pct['p99_ms']:.1f}ms")

    record = {"meta": {}, "rows": []}
    if os.path.exists(args.out):
        with open(args.out) as f:
            record = json.load(f)
    kept = [r for r in record.get("rows", []) if r.get("bench") != "serve"]
    record["rows"] = kept + rows
    record.setdefault("meta", {})["serve_cpu_count"] = cpu_count
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {len(rows)} serve rows into {args.out}")


if __name__ == "__main__":
    main()
