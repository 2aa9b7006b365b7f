"""Smoke run of the FFCz service on TPU, through the entry points users call.

    python chip_smoke.py            # one chip: the service at Nyx scale
    python chip_smoke.py --chips 4  # four chips: slab-sharded field vs one chip

One chip: builds an ``FFCzService`` with the ``launch/serve_ffcz.py``
builders, compresses a 512^3 float32 Nyx-like cosmology field (SDRBench's
Nyx size) once per POCS transform (``fft_impl="xla"`` and ``"pallas"``),
compresses one fused batch of EEG-like channels x time pencil requests,
decodes every blob, and rechecks both stored bounds of each decoded result
in float64 numpy:

    max|x_hat - x| <= E   and   max(|Re|, |Im|) of rfftn(x_hat - x) <= Delta

(per pencil for the pencil batch).  A response that took any ladder rung,
or ran another transform than the one requested, fails the run.

``--chips 4``: ``FFCz.compress`` of the same field slab-sharded over a
four-chip mesh against ``FFCz.compress`` on one chip.  512^3 is a
``"bitwise"`` parity class, so the two blob payloads must be
byte-identical; both bounds are rechecked on every distinct decoded blob.

Lines before the last are bring-up notes (iterations, stage clocks, compile
time, peak device memory), not metrics.  The last line is one JSON object
naming the device.  Exits nonzero, with no JSON line, when JAX finds no TPU
or any phase fails.  JAX's persistent compile cache goes where
``repro.launch.compile_cache`` says.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import struct
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np  # noqa: E402
from scipy import fft as host_fft  # noqa: E402

from repro.compressors import get_compressor  # noqa: E402
from repro.configs.ffcz_fields import FIELDS  # noqa: E402
from repro.core.engine import CorrectionEngine  # noqa: E402
from repro.core.ffcz import PENCIL_HEADER, PENCIL_MAGIC, FFCz, FFCzBlob, FFCzConfig  # noqa: E402
from repro.data.fields import make_field  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve_ffcz import add_fault_args, add_service_args, build_service  # noqa: E402

#: SDRBench's Nyx fields are 512^3 float32.
NYX_EDGE = 512
#: EEG-like pencil batch: requests x (channels, samples); one pencil per
#: channel row (the service block is the time axis).
EEG_REQUESTS, EEG_CHANNELS, EEG_SAMPLES = 8, 64, 512
E_REL = DELTA_REL = 1e-3
#: Covers a cold compile of the 512^3 program plus its host stages.
DEADLINE_S = 1200.0


class CompileClock:
    """Sums JAX's own compile-duration events (trace, lower, backend)."""

    def __init__(self):
        self.seconds = {}

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            name = event.rsplit("/", 1)[-1]
            self.seconds[name] = self.seconds.get(name, 0.0) + duration

    def note(self) -> str:
        return ", ".join(f"{k}={v:.1f}s" for k, v in sorted(self.seconds.items()))


def require_tpu(chips: int) -> dict:
    """The device JAX reports; exits nonzero unless it is a TPU with enough chips."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} count={len(devs)}", flush=True)
    if d0.platform != "tpu":
        raise SystemExit(f"no TPU: JAX reports platform {d0.platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices, JAX reports {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def nyx_field(edge: int, seed: int) -> np.ndarray:
    """Nyx-like lognormal density cube, ``edge``^3 float32, from ``seed``."""
    cfg = dataclasses.replace(
        FIELDS["nyx-like"], name=f"nyx-{edge}", shape=(edge,) * 3, seed=seed
    )
    return make_field(cfg)


def eeg_batch(n: int, channels: int, samples: int, seed: int):
    """EEG-like channels x time recordings (random-walk drift + a shared
    oscillation + sensor noise)."""
    rng = np.random.default_rng(seed)
    t = np.linspace(0, 6 * np.pi, samples)[None, :]
    return [
        np.ascontiguousarray(
            (rng.standard_normal((channels, samples)) * 0.3).cumsum(axis=1)
            + np.sin(t + i)
            + 0.01 * rng.standard_normal((channels, samples)),
            np.float32,
        )
        for i in range(n)
    ]


def check_bounds(label: str, x: np.ndarray, x_hat: np.ndarray, E: float, Delta: float,
                 axes=None) -> None:
    """Float64 recheck of both stored bounds; ``axes`` are the transform
    axes (all for a whole field, the pencil axis for pencils).  The spectrum
    of the difference is the difference of the spectra (linearity), computed
    without cancelling two large spectra against each other."""
    err = x_hat.astype(np.float64) - x.astype(np.float64)
    e_max = float(np.max(np.abs(err)))
    d = host_fft.rfftn(err, axes=axes, workers=-1)
    f_max = float(np.max(np.maximum(np.abs(d.real), np.abs(d.imag))))
    print(f"  {label}: max|err|={e_max:.6g} <= E={E:.6g}; "
          f"max spectral err={f_max:.6g} <= Delta={Delta:.6g}", flush=True)
    if not (e_max <= E and f_max <= Delta):
        raise AssertionError(f"{label}: a stored bound does not hold on the decoded data")


def pencil_bounds(blob: bytes):
    """(E, Delta, block) from a service pencil blob's header."""
    assert blob[:4] == PENCIL_MAGIC, "not a pencil blob"
    E, Delta, block, _ndim = struct.unpack_from(PENCIL_HEADER, blob, 5)
    return E, Delta, block


def require_clean(uid: str, resp, fft_impl=None) -> None:
    """A response must succeed with no rung taken and the requested transform."""
    if not resp.ok:
        raise AssertionError(f"{uid} rejected: {resp.error}")
    if resp.stats.rungs:
        raise AssertionError(f"{uid} took ladder rungs {resp.stats.rungs}")
    if fft_impl is not None and resp.stats.fft_impl != fft_impl:
        raise AssertionError(f"{uid} ran fft_impl={resp.stats.fft_impl}, asked {fft_impl}")


def service_args(seed: int, block: int) -> argparse.Namespace:
    """The serving flags at their defaults (no faults injected), with a
    deadline that covers a cold compile and the pencil ``block`` set to the
    EEG time axis."""
    ap = argparse.ArgumentParser()
    add_service_args(ap)
    add_fault_args(ap)
    ap.set_defaults(seed=seed, deadline_s=DEADLINE_S, block=block)
    return ap.parse_args([])


def run_service(seed: int, edge: int, eeg=(EEG_REQUESTS, EEG_CHANNELS, EEG_SAMPLES)) -> None:
    """The one-chip phases: field requests per transform, a pencil batch,
    decode of every blob, and the float64 bound rechecks."""
    svc_args = service_args(seed, block=eeg[2])
    t0 = time.perf_counter()
    x = nyx_field(edge, seed)
    pencils = eeg_batch(*eeg, seed=seed + 1)
    print(f"data: nyx {x.shape} float32 + {len(pencils)} pencil requests "
          f"{pencils[0].shape} in {time.perf_counter() - t0:.1f}s", flush=True)

    svc = build_service(svc_args)
    try:
        impls = {}
        for impl in ("xla", "pallas"):
            cfg = FFCzConfig(E_rel=E_REL, Delta_rel=DELTA_REL, fft_impl=impl, verify=False)
            impls[svc.submit_compress(x, cfg, uid=f"field-{impl}")] = impl
        for i, p in enumerate(pencils):
            svc.submit_pencils(p, E_REL, DELTA_REL, uid=f"pencils-{i}")
        t0 = time.perf_counter()
        done = svc.drain()
        print(f"compress drained in {time.perf_counter() - t0:.1f}s", flush=True)
        for uid, r in done.items():
            require_clean(uid, r, impls.get(uid))
            print(f"  {uid}: iterations={r.stats.iterations} converged={r.stats.converged} "
                  f"fft_impl={r.stats.fft_impl} bytes={len(r.payload)} "
                  f"latency={r.stats.latency_s:.1f}s", flush=True)
            if not r.stats.converged:
                raise AssertionError(f"{uid} did not converge")

        dec = {svc.submit_decompress(r.payload, uid=f"dec-{uid}"): uid for uid, r in done.items()}
        t0 = time.perf_counter()
        decoded = svc.drain()
        print(f"decompress drained in {time.perf_counter() - t0:.1f}s", flush=True)
        print(f"stage clocks (s): { {k: round(v, 2) for k, v in svc.timers.items()} }")
        print(f"counters: {dict(svc.counters)}", flush=True)
    finally:
        svc.close()

    print("float64 bound recheck:", flush=True)
    for duid, uid in dec.items():
        r = decoded[duid]
        require_clean(duid, r)
        blob = done[uid].payload
        if uid.startswith("field-"):
            b = FFCzBlob.from_bytes(blob)
            check_bounds(uid, x, r.payload, b.E, b.Delta_scalar)
        else:
            E, Delta, block = pencil_bounds(blob)
            src = pencils[int(uid.rsplit("-", 1)[1])]
            if src.shape[-1] != block:
                raise AssertionError(f"{uid}: block {block} is not the time axis")
            check_bounds(uid, src, r.payload, E, Delta, axes=(-1,))


def run_sharded(edge: int, seed: int, devices) -> None:
    """``--chips 4``: the slab-sharded whole field vs the one-chip field."""
    from repro.launch.mesh import make_mesh
    from repro.sharding.dist_fft import ShardedField

    t0 = time.perf_counter()
    x = nyx_field(edge, seed)
    n = len(devices)
    field = ShardedField.shard(x, make_mesh((n,), ("data",), devices=devices))
    print(f"data: nyx {x.shape} float32 in {time.perf_counter() - t0:.1f}s, "
          f"{n}-device parity class {field.parity!r}", flush=True)

    def compress(arg):
        """FFCz.compress on an engine of its own, with the loop result that
        compress keeps to itself noted on the way."""
        engine = CorrectionEngine(backend="batched")
        results = []
        execute = engine.execute_field
        engine.execute_field = lambda *a, **k: results.append(execute(*a, **k)) or results[-1]
        codec = FFCz(get_compressor("szlike"),
                     FFCzConfig(E_rel=E_REL, Delta_rel=DELTA_REL, verify=False), engine)
        t0 = time.perf_counter()
        blob = codec.compress(arg)
        return codec, blob, results[-1], time.perf_counter() - t0

    # The two runs share no state, and most of each is host work (base
    # codec, float64 polish, encode) that overlaps the other's.
    labels = ("one chip", f"{n} chips")
    with ThreadPoolExecutor(2) as pool:
        runs = dict(zip(labels, pool.map(compress, (x, field))))
    for label, (_codec, _blob, r, seconds) in runs.items():
        print(f"{label}: FFCz.compress in {seconds:.1f}s (both at once), "
              f"iterations={r.iterations} converged={r.converged}", flush=True)
        if not r.converged:
            raise AssertionError(f"{label}: the correction did not converge")
    codec, one, _r, _s = runs[labels[0]]
    sharded = runs[labels[1]][1]

    p1, pn = one.payload_bytes(), sharded.payload_bytes()
    same = p1 == pn
    print(f"payload bytes: one chip {len(p1)}, {n} chips {len(pn)}, identical={same}",
          flush=True)
    # identical payloads decode identically, so one recheck covers both
    print("float64 bound recheck:", flush=True)
    check_bounds(f"sharded-{n}", x, codec.decompress(sharded), sharded.E, sharded.Delta_scalar)
    if not same:
        check_bounds("one-chip", x, codec.decompress(one), one.E, one.Delta_scalar)
        if field.parity == "bitwise":
            raise AssertionError("sharded payload differs from the one-chip payload")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the service phases; 4: only the slab-sharded phase")
    ap.add_argument("--seed", type=int, default=0, help="seed of the generated data")
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    device = require_tpu(args.chips)
    import jax

    print(f"compile cache: {cache}", flush=True)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    t0 = time.perf_counter()
    if args.chips == 4:
        run_sharded(NYX_EDGE, args.seed, jax.devices()[:4])
    else:
        run_service(args.seed, NYX_EDGE)
    print(f"wall {time.perf_counter() - t0:.1f}s; compile: {clock.note()}")
    for d in jax.devices()[: args.chips]:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"peak device memory {d.id}: "
              f"{'not reported' if peak is None else f'{peak / 2**30:.2f} GiB'}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
