"""FFCz-compressed array codec for checkpoints (DESIGN.md §3 integration #1).

Float arrays are compressed with a base compressor + FFCz dual-domain
correction: the spatial bound controls pointwise weight error (restart
quality), the frequency bound preserves each tensor's spectrum — for weight
matrices that is the quantity tied to the layer's singular-value structure.
Non-float / tiny arrays pass through raw.

Both encode paths are clients of :class:`repro.core.engine.CorrectionEngine`
and share the wire envelope; this module owns ONLY workload shaping and byte
assembly (bound discipline, POCS, bit-width and pair-weight math all live in
the engine):

``encode``        — tag ``F``: whole-array FFCz (the paper pipeline via
                    :class:`repro.core.ffcz.FFCz`; the frequency bound
                    applies to the array's global spectrum).
``encode_batch``  — tag ``B``: blockwise FFCz for a whole checkpoint at
                    once.  Per leaf, ``engine.plan_pencils`` resolves the
                    per-pencil bounds, then ALL leaves' base-compression
                    errors are corrected by a single batched (or, with a
                    sharded engine, ``shard_map``-distributed) device
                    program via ``engine.correct``, and
                    ``engine.encode_pencils`` polishes + serializes each
                    leaf's rfft half-spectrum edit streams.

Both tags decode through :meth:`CheckpointCodec.decode`; raw arrays use
tag ``R``.
"""

from __future__ import annotations

import io
import struct
from typing import List, Optional, Sequence

import numpy as np

from repro.compressors import get_compressor
from repro.core.edits import EncodedEdits, decode_edits
from repro.core.engine import CorrectionEngine, default_engine
from repro.core.ffcz import FFCz, FFCzBlob, FFCzConfig

_RAW = b"R"
_FFZ = b"F"
_FFB = b"B"  # blockwise-batched FFCz (rfft half-spectrum edit streams)

_DTYPE_CODES = {"float32": 0, "float64": 1}


class CheckpointCodec:
    def __init__(
        self,
        enabled: bool = True,
        E_rel: float = 1e-4,
        Delta_rel: float = 1e-4,
        base: str = "szlike",
        min_size: int = 4096,
        max_iters: int = 50,
        block: int = 4096,
        engine: Optional[CorrectionEngine] = None,
    ):
        self.enabled = enabled
        self.min_size = min_size
        self.E_rel = E_rel
        self.Delta_rel = Delta_rel
        self.max_iters = max_iters
        self.block = block
        self.base = get_compressor(base)
        self.engine = engine or default_engine()
        self.ffcz = FFCz(
            self.base,
            FFCzConfig(E_rel=E_rel, Delta_rel=Delta_rel, max_iters=max_iters, codec="zlib", verify=False),
            engine=self.engine,
        )

    def _eligible(self, arr: np.ndarray) -> bool:
        return (
            self.enabled
            and arr.dtype in (np.float32, np.float64)
            and arr.size >= self.min_size
            and np.ptp(arr) > 0
        )

    @staticmethod
    def _raw(arr: np.ndarray) -> bytes:
        buf = io.BytesIO()
        np.save(buf, arr, allow_pickle=False)
        return _RAW + buf.getvalue()

    # -- whole-array path (paper pipeline) ---------------------------------

    def encode(self, arr: np.ndarray) -> bytes:
        arr = np.asarray(arr)
        if not self._eligible(arr):
            return self._raw(arr)
        blob = self.ffcz.compress(arr.astype(np.float32))
        payload = blob.to_bytes()
        header = struct.pack("<B", _DTYPE_CODES[str(arr.dtype)])
        return _FFZ + header + payload

    # -- batched blockwise path --------------------------------------------

    def encode_batch(self, arrays: Sequence[np.ndarray]) -> List[bytes]:
        """Encode a whole checkpoint's leaves with ONE batched correction.

        Semantics differ from :meth:`encode` only in the frequency bound's
        scope: Delta applies to each ``block``-length pencil's local rfft
        spectrum (Delta = Delta_rel * max |RFFT(pencil of x)|, per array)
        instead of the array's global spectrum.  The spatial bound E holds
        at every point; the frequency bound holds per *full* pencil (an
        array whose size is not a multiple of ``block`` has its tail pencil
        corrected on a zero-padded extension that decode discards).
        """
        arrays = [np.asarray(a) for a in arrays]
        idx = [i for i, a in enumerate(arrays) if self._eligible(a)]
        eligible = set(idx)
        out: List[bytes] = [b"" for _ in arrays]
        for i, a in enumerate(arrays):
            if i not in eligible:
                out[i] = self._raw(a)
        if not idx:
            return out

        block = self.block
        errs = []  # base-compression error tensors, consumed by engine.correct
        work = []  # (leaf index, base_blob, float64 tiling, PencilPlan)
        for i in idx:
            x32 = arrays[i].astype(np.float32)
            plan = self.engine.plan_pencils(
                x32, E_rel=self.E_rel, Delta_rel=self.Delta_rel, block=block
            )
            if plan is None:
                # range below float32 representability — store raw instead
                out[i] = self._raw(arrays[i])
                continue
            base_blob = self.base.compress(x32, plan.E_proj)
            x_hat = np.asarray(self.base.decompress(base_blob), dtype=np.float32)
            eps0 = x_hat - x32
            # float64 tiling captured up front: the polish rebuilds the loop
            # state from it, so eps0 itself need not outlive the batched call
            tiles0 = self.engine.tile_f64(eps0, block)
            errs.append(eps0)
            work.append((i, base_blob, tiles0, plan))

        if not work:
            return out
        _corr, edits, _stats = self.engine.correct(
            errs,
            [w[3].E_proj for w in work],
            [w[3].Delta_proj for w in work],
            block=block,
            max_iters=self.max_iters,
            return_edits=True,
            return_corrected=False,  # only the edit streams are serialized
        )
        del errs  # free the float32 error copies; tiles0 carries the state

        for (i, base_blob, tiles0, plan), (spat_t, freq_t) in zip(work, edits):
            se, fe, _settled = self.engine.encode_pencils(spat_t, freq_t, tiles0, plan, codec="zlib")
            se_b, fe_b = se.to_bytes(), fe.to_bytes()
            arr = arrays[i]
            header = struct.pack(
                "<BddIB",
                _DTYPE_CODES[str(arr.dtype)],
                plan.E,
                plan.Delta,
                block,
                arr.ndim,
            )
            header += struct.pack(f"<{arr.ndim}Q", *arr.shape)
            header += struct.pack("<QQQ", len(base_blob), len(se_b), len(fe_b))
            out[i] = _FFB + header + base_blob + se_b + fe_b
        return out

    def _decode_ffb(self, body: bytes) -> np.ndarray:
        dt_code, E, Delta, block, ndim = struct.unpack_from("<BddIB", body, 0)
        off = struct.calcsize("<BddIB")
        shape = struct.unpack_from(f"<{ndim}Q", body, off)
        off += 8 * ndim
        nb, ns, nf = struct.unpack_from("<QQQ", body, off)
        off += struct.calcsize("<QQQ")
        base_blob = body[off : off + nb]
        off += nb
        se = EncodedEdits.from_bytes(body[off : off + ns])
        off += ns
        fe = EncodedEdits.from_bytes(body[off : off + nf])
        x_hat = np.asarray(self.base.decompress(base_blob), dtype=np.float32)
        spat = decode_edits(se, E)  # (n_blocks, block)
        freq = decode_edits(fe, Delta)  # (n_blocks, block//2+1) half-spectra
        complete = spat + np.fft.irfft(freq, n=block, axis=-1)
        size = int(np.prod(shape)) if shape else 1
        x = x_hat.astype(np.float64).reshape(-1) + complete.reshape(-1)[:size]
        out = x.reshape(shape).astype(np.float32)
        return out.astype(np.float64 if dt_code == 1 else np.float32)

    # -- decode (all tags) -------------------------------------------------

    def decode(self, data: bytes) -> np.ndarray:
        tag, body = data[:1], data[1:]
        if tag == _RAW:
            return np.load(io.BytesIO(body), allow_pickle=False)
        if tag == _FFB:
            return self._decode_ffb(body)
        (dt_code,) = struct.unpack_from("<B", body, 0)
        blob = FFCzBlob.from_bytes(body[1:])
        out = self.ffcz.decompress(blob)
        return out.astype(np.float64 if dt_code == 1 else np.float32)
