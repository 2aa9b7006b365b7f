"""Pencil requests: ``FFCzService.submit_pencils``, one window per request.

A request of size ``k`` is a ``(channels, k * block)`` window, tiled by the
service into ``channels * k`` pencils of ``block`` samples (the time axis).
A pool of ``pool_per_size`` windows per size is made per run and cycled.

The service fuses up to ``max_batch`` queued requests into one device call
and sizes it exactly, so every bucket composition is a shape of its own:
the program is compiled per (pencils in the bucket, requests in it), and
the per-request slices of its outputs per (pencils, offset, length).
:meth:`Kind.warm` runs each of them once before the window.
"""

from __future__ import annotations

import itertools
import struct
from typing import Dict, List

import numpy as np

from perfbench import reference

_HEADER = "<dd"  # E, Delta at byte 5 of a service pencil blob (after magic + version)


class Kind:
    def __init__(self, cfg: dict, gen, traffic: dict, seed: int):
        self.cfg, self.gen, self.traffic, self.seed = cfg, gen, traffic, seed
        self.block = int(cfg["block"])
        self.sizes = sorted((traffic.get("sizes") or {"1": 1.0}), key=int)
        self.pool: Dict[str, List[np.ndarray]] = {}
        self._refs: Dict[tuple, tuple] = {}

    def prepare(self) -> None:
        n = int(self.cfg["pool_per_size"])
        self.pool = {s: [self.gen.make(self.cfg, s, i, self.seed) for i in range(n)]
                     for s in self.sizes}

    def data(self, req) -> np.ndarray:
        pool = self.pool[req.size]
        return pool[req.index % len(pool)]

    def in_bytes(self, req) -> int:
        return int(self.data(req).nbytes)

    def submit(self, svc, req) -> None:
        svc.submit_pencils(self.data(req), self.cfg["E_rel"], self.cfg["Delta_rel"], uid=req.uid)

    def _rows(self, size: str) -> int:
        """Pencils of one request: a window of ``size`` blocks per channel."""
        return int(self.cfg["channels"]) * int(size)

    def warm(self, svc) -> None:
        """Compile every bucket program and output slice the mix can form,
        then send one request of each size through the whole service."""
        import jax

        from repro.core import blockwise

        engine, conf = svc.engine, svc.config
        rows = [self._rows(s) for s in self.sizes]
        outputs = {}
        for n in range(1, conf.max_batch + 1):
            for comp in itertools.combinations_with_replacement(rows, n):
                b = sum(comp)
                if (b, n) in outputs:
                    continue
                # the service's own call, as _dispatch_bucket makes it
                tensors = [np.zeros(r * self.block, np.float32) for r in comp]
                engine.correct_async(tensors, [1.0] * n, [1.0] * n, block=self.block,
                                     max_iters=conf.max_iters, return_edits=True,
                                     return_corrected=False).result()
                outputs[b, n] = comp
        for b in sorted({b for b, _ in outputs}):
            # the handle slices each request's rows out of the program's
            # outputs; every (offset, length) compiles once per bucket size
            n = min(n for bb, n in outputs if bb == b)
            comp = outputs[b, n]
            packed = np.zeros((b, self.block), np.float32)
            res, _stats = blockwise.correct_packed(
                packed, list(comp), [1.0] * n, [1.0] * n, max_iters=conf.max_iters,
                backend=engine.backend, axis=engine.axis, fft_impl=engine.fft_impl)
            for off in range(0, b, min(rows)):
                for nb in rows:
                    if off + nb <= b:
                        jax.block_until_ready((res.spat_edits[off:off + nb],
                                               res.freq_edits[off:off + nb]))
        for s in self.sizes:
            svc.submit_pencils(self.pool[s][0], self.cfg["E_rel"], self.cfg["Delta_rel"],
                               uid=f"warm-{s}")
        svc.drain()

    def largest(self, reqs) -> list:
        top = max((int(r.size) for r in reqs), default=0)
        return [r for r in reqs if int(r.size) == top]

    def _bounds(self, req, precision: str) -> tuple:
        key = (req.size, req.index % len(self.pool[req.size]), precision)
        if key not in self._refs:
            self._refs[key] = reference.bounds(self.data(req), self.cfg["E_rel"],
                                               self.cfg["Delta_rel"], self.block, precision)
        return self._refs[key]

    def check(self, svc, req, control: bool = False) -> dict:
        """``{"program": readings}`` for one response, and ``"control"``
        readings when asked for."""
        from repro.serving.ffcz_service import decode_pencil_blob

        payload = req.resp.payload
        stored = struct.unpack_from(_HEADER, payload, 5)
        x_hat = decode_pencil_blob(payload, svc.base)
        errs = reference.errors(self.data(req), x_hat, self.block)
        ref = self._bounds(req, "float64")
        out = {"program": reference.compare(stored, ref, errs)}
        if control:
            out["control"] = reference.compare(self._bounds(req, "bfloat16"), ref, errs)
        return out
