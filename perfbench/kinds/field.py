"""Whole-field requests: ``FFCzService.submit_compress``, one field per request.

The configuration names the field's generator (``configs/<name>.py``), its
bounds and ``distinct_fields``, the number of fields made per run and
cycled.  The check decodes sampled blobs with the program's own decoder and
holds them to the float64 reference (``perfbench/reference.py``).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from perfbench import reference


class Kind:
    def __init__(self, cfg: dict, gen, traffic: dict, seed: int):
        self.cfg, self.gen, self.traffic, self.seed = cfg, gen, traffic, seed
        self.fields: List[np.ndarray] = []
        self._refs: Dict[tuple, tuple] = {}

    def prepare(self) -> None:
        self.fields = [
            self.gen.make(self.cfg, "1", i, self.seed) for i in range(int(self.cfg["distinct_fields"]))
        ]

    def data(self, req) -> np.ndarray:
        return self.fields[req.index % len(self.fields)]

    def in_bytes(self, req) -> int:
        return int(self.data(req).nbytes)

    def _request_config(self):
        from repro.core.ffcz import FFCzConfig

        return FFCzConfig(E_rel=self.cfg["E_rel"], Delta_rel=self.cfg["Delta_rel"],
                          fft_impl=self.cfg.get("fft_impl", "xla"), verify=False)

    def submit(self, svc, req) -> None:
        svc.submit_compress(self.data(req), self._request_config(), uid=req.uid)

    def warm(self, svc) -> None:
        """A closed loop warms on its own first completion, which opens the
        window; an open loop first compresses one field of every index."""
        if self.traffic.get("loop") == "closed":
            return
        for i, x in enumerate(self.fields):
            svc.submit_compress(x, self._request_config(), uid=f"warm-{i}")
        svc.drain()

    def largest(self, reqs) -> list:
        return list(reqs)

    def _bounds(self, i: int, precision: str) -> tuple:
        key = (i % len(self.fields), precision)
        if key not in self._refs:
            self._refs[key] = reference.bounds(self.fields[key[0]], self.cfg["E_rel"],
                                               self.cfg["Delta_rel"], precision=precision)
        return self._refs[key]

    def check(self, svc, req, control: bool = False) -> dict:
        """``{"program": readings}`` for one response, and ``"control"``
        readings when asked for."""
        from repro.core.ffcz import FFCz, FFCzBlob, FFCzConfig

        blob = FFCzBlob.from_bytes(req.resp.payload)
        x_hat = FFCz(svc.base, FFCzConfig(), engine=svc.engine).decompress(blob)
        errs = reference.errors(self.data(req), x_hat)
        ref = self._bounds(req.index, "float64")
        out = {"program": reference.compare((blob.E, blob.Delta_scalar), ref, errs)}
        if control:
            out["control"] = reference.compare(self._bounds(req.index, "bfloat16"), ref, errs)
        return out
