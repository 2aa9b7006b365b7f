"""FFCz public codec: a thin plan/execute/encode client of the CorrectionEngine.

This is the end-to-end pipeline of the paper (Fig. 4 / Alg. 1), expressed as
the three engine stages of :class:`repro.core.engine.CorrectionEngine`:

  compress(x):
    1. PLAN     engine.plan_field(x, cfg)   -> bounds resolved on device,
                float32/quantization discipline applied, pointwise Delta_k
                grids built from a device rfft (and only when a bound
                actually consumes the spectrum — Delta_abs skips the
                forward FFT entirely)
    2.          base_residual(base, x, E_proj)
                -> base blob (spatially bounded) + its error x_hat - x
    3. EXECUTE  engine.execute_field(x_hat - x, plan)
                -> one jitted device POCS program (Hermitian rfft
                half-spectrum loop) + exact float64 polish
    4. ENCODE   engine.encode_field(result, plan)
                -> pair-weighted adaptive bit-widths, flags + quantized +
                Huffman/zlib edit streams
    5.          byte assembly (FFCzBlob)

  decompress(blob):
    x_hat_base + spat_edits + IRFFT(freq_edits)
    (the "complete spatial edits" of §IV-B)

The class owns only what is irreducibly codec-shaped: base-compressor I/O,
post-hoc verification, and the wire format.  All bound discipline,
projection, pair-weight and bit-width math lives in the engine, shared with
the pencil-tiled checkpoint/KV/gradient paths.

Wire format: blobs carry a ``FFCZ`` magic + version byte (version 1) and
length-validated section table; version-0 (magic-less) blobs from older
writers are sniffed and still decode, including legacy full-spectrum
frequency streams (``EncodedEdits.half_spectrum`` clear) via the ``ifftn``
branch of :meth:`FFCz.decompress`.

The pencil envelope ``FFSB`` (:func:`encode_pencil_blob` /
:func:`decode_pencil_blob`) is the other FFCz wire format: one pencil-planned
tensor, as the service's pencil responses and the temporal codec's
pencil-mode frames carry it.
"""

from __future__ import annotations

import dataclasses
import struct
import zlib
from typing import Any, Optional

import numpy as np

from repro.coding.quantize import DEFAULT_QUANT_BITS
from repro.compressors import base_residual
from repro.core.cubes import rfft_shape
from repro.core.edits import EncodedEdits, decode_edits
from repro.core.errors import BlobCorruptError, FFCzError
from repro.core.engine import (  # re-exported for backward compatibility
    CorrectionEngine,
    adaptive_quant_bits,
    default_engine,
    float32_bound_discipline,
    polish_pocs_float64,
)
from repro.sharding.dist_fft import ShardedField

__all__ = [
    "BlobCorruptError",
    "FFCz",
    "FFCzBlob",
    "FFCzConfig",
    "FFCzStats",
    "PadMeta",
    "ShardedField",
    "adaptive_quant_bits",
    "decode_pencil_blob",
    "encode_pencil_blob",
    "float32_bound_discipline",
    "polish_pocs_float64",
]


@dataclasses.dataclass(frozen=True)
class FFCzConfig:
    """User-facing dual-domain bound configuration.

    Exactly one of (E_abs, E_rel) and one of (Delta_abs, Delta_rel,
    pspec_rel) must be set.  ``pspec_rel`` activates the per-component
    power-spectrum-preserving bounds of Observation 4.
    """

    E_abs: Optional[float] = None
    E_rel: Optional[float] = 1e-3
    Delta_abs: Optional[float] = None
    Delta_rel: Optional[float] = 1e-3
    pspec_rel: Optional[float] = None
    # ROI bounds (region-aware spatial guarantees): a boolean mask (True =
    # region of interest, bound tightened to E * E_roi_scale) or a float
    # grid of per-point absolute bounds (entries <= 0 mean background E),
    # field-shaped.  See repro.core.bounds.resolve_roi_bound_grid.  The
    # resolved float32 E_n grid rides the blob in an optional FFCR tail
    # section; None (default) keeps uniform-E blobs byte-identical to
    # earlier writers.
    E_roi: Optional[Any] = None
    E_roi_scale: float = 0.1
    # Floor for pointwise Delta_k, relative to max_k Delta_k.  Near-dead
    # frequency components contribute nothing to P(k); flooring their bound
    # keeps the f-cube from becoming needle-thin along dead axes, which is
    # the slow nearly-tangential POCS regime (paper §III).
    pspec_floor_rel: float = 1e-4
    quant_bits: int = DEFAULT_QUANT_BITS
    max_iters: int = 1000
    codec: str = "huffman+zlib"
    use_kernels: bool = False
    verify: bool = True
    # Over-relaxation factor for the POCS loop (1.0 = paper-faithful plain
    # alternating projection; ~1.3 converges orders of magnitude faster in
    # the nearly-tangential regime — see EXPERIMENTS.md §Perf FFCz-iter).
    relax: float = 1.0
    # POCS loop transform selector: "xla" (default; blobs byte-identical to
    # earlier writers), "packed" (pack-trick C2R inverse — the measured CPU
    # fast path), or "pallas" (packed + fused clip/count epilogue kernels).
    # See repro.core.pocs / repro.kernels.rfft.  Non-"xla" impls are
    # "bound"-parity: sharded blobs may diverge from single-device ones at
    # float32-rounding level while the dual-bound guarantee holds.
    fft_impl: str = "xla"
    # Run the POCS convergence-check reduction every K-th iteration (the
    # final iteration always checks).  Extra iterations are always safe, so
    # K > 1 trades up-to-K-1 late convergence for one reduction (and one
    # psum, in distributed mode) per skipped iteration.
    check_every: int = 1
    # Temporal warm start (see repro.core.temporal / docs/streaming.md):
    # when True, execute_field seeds the POCS loop's freq_edits state from a
    # caller-supplied previous-frame spectrum.  False (default) ignores any
    # warm state — the bitwise-identical cold start, so non-stream callers
    # and disabled streams produce byte-identical blobs.
    warm_start: bool = False
    # Append a per-section CRC32 tail (``FFCC`` marker) to written blobs so
    # bit flips that structural validation cannot see are caught at decode.
    # Off by default: the tail changes the blob bytes, and the default path
    # stays byte-identical to earlier writers.  Decoding verifies the tail
    # whenever one is present, regardless of this flag.
    crc: bool = False
    # Derived-quantity verify-after-polish (pspec mode only): recheck in
    # float64 that every live shell's power-spectrum ratio satisfies
    # |P_hat(k)/P(k) - 1| <= pspec_rel on the decoded field, surfaced as
    # FFCzStats.pspec_shell_err / pspec_shell_ok.  Opt-in: it costs two
    # full-field float64 FFTs on the host.
    verify_pspec: bool = False

    def __post_init__(self):
        if (self.E_abs is None) == (self.E_rel is None):
            raise ValueError("exactly one of E_abs / E_rel required")
        n_freq = sum(x is not None for x in (self.Delta_abs, self.Delta_rel, self.pspec_rel))
        if n_freq != 1:
            raise ValueError("exactly one of Delta_abs / Delta_rel / pspec_rel required")
        if self.fft_impl not in ("xla", "packed", "pallas"):
            raise ValueError(
                f"fft_impl must be 'xla', 'packed' or 'pallas', got {self.fft_impl!r}"
            )
        if self.check_every < 1:
            raise ValueError(f"check_every must be >= 1, got {self.check_every}")
        if not 0.0 < self.E_roi_scale <= 1.0:
            raise ValueError(f"E_roi_scale must be in (0, 1], got {self.E_roi_scale}")


@dataclasses.dataclass(frozen=True)
class FFCzStats:
    iterations: int
    converged: bool
    n_active_spatial: int
    n_active_frequency: int
    base_bytes: int
    edit_bytes: int
    spatial_margin: float  # min(E - |eps|) over points, >= 0 means bound held
    frequency_margin: float  # min(Delta - max(|Re d|,|Im d|)), >= 0 means held
    # Pair-weighted count of frequency components still outside the shrunk
    # f-cube after the float64 polish; 0 whenever ``converged``.  Non-zero
    # means the POCS budget ran out: the spatial bound still holds, the
    # frequency bound is violated at exactly this many components.
    final_violations: int = 0
    # Derived-quantity shell recheck (cfg.verify_pspec, pspec mode only):
    # max over live shells of |P_hat(k)/P(k) - 1| measured in float64 on the
    # decoded field, and whether it sits within the claimed pspec_rel.
    # None when the recheck did not run.
    pspec_shell_err: Optional[float] = None
    pspec_shell_ok: Optional[bool] = None

    @property
    def total_bytes(self) -> int:
        return self.base_bytes + self.edit_bytes


_MAGIC = b"FFCZ"
_WIRE_VERSION = 1
_V0_HEADER = "<ddBQQQQ"  # E, Delta_scalar, ndim, len(base), len(se), len(fe), len(pw)
_PAD_MAGIC = b"FFCP"
_PAD_HEADER = "<IB"  # n_dev (u32), ndim (u8); then ndim * u64 padded shape
# Optional ROI spatial-bound section (sniffed like FFCP): u64 byte count,
# then the float32 per-point E_n grid in field shape/order.
_ROI_MAGIC = b"FFCR"
# Optional integrity tail (sniffed like FFCP): u8 count, then count * u32
# CRC32s — whole-blob-so-far, base, spat_edits, freq_edits, pointwise.
_CRC_MAGIC = b"FFCC"
_CRC_SECTIONS = ("header", "base", "spat_edits", "freq_edits", "pointwise")


@dataclasses.dataclass(frozen=True)
class PadMeta:
    """Slab-decomposition provenance of a blob written from an uneven
    :class:`~repro.sharding.dist_fft.ShardedField`.

    Purely informational: the edit streams are always encoded at the true
    field extents, so decoding never needs this — it records how the writer
    padded and sharded the field (mesh axis size + padded device shape) for
    tooling and re-scatter hints.  Serialized as an OPTIONAL trailing blob
    section introduced by a ``FFCP`` marker, sniffed by its presence exactly
    like the v0 magic sniff — older v1 blobs (no section) and v0 blobs
    parse unchanged, and this section's absence keeps evenly-decomposed and
    single-device blobs byte-identical to pre-pad writers.
    """

    n_dev: int
    padded_shape: tuple

    def to_bytes(self) -> bytes:
        return (
            _PAD_MAGIC
            + struct.pack(_PAD_HEADER, self.n_dev, len(self.padded_shape))
            + struct.pack(f"<{len(self.padded_shape)}Q", *self.padded_shape)
        )

    @staticmethod
    def from_bytes(data: bytes) -> "PadMeta":
        meta, end = PadMeta._parse_at(data, 0)
        if end != len(data):
            raise BlobCorruptError("corrupt FFCz blob: malformed pad-metadata section")
        return meta

    @staticmethod
    def _parse_at(data: bytes, pos: int) -> tuple:
        """Parse one FFCP section starting at ``pos``; returns (meta, end)."""
        head = pos + len(_PAD_MAGIC) + struct.calcsize(_PAD_HEADER)
        if len(data) < head or data[pos : pos + len(_PAD_MAGIC)] != _PAD_MAGIC:
            raise BlobCorruptError(
                "corrupt FFCz blob: trailing bytes are not a pad-metadata section"
            )
        n_dev, ndim = struct.unpack_from(_PAD_HEADER, data, pos + len(_PAD_MAGIC))
        if ndim > 16 or len(data) < head + 8 * ndim:
            raise BlobCorruptError("corrupt FFCz blob: malformed pad-metadata section")
        shape = struct.unpack_from(f"<{ndim}Q", data, head)
        return PadMeta(n_dev=n_dev, padded_shape=tuple(shape)), head + 8 * ndim


@dataclasses.dataclass(frozen=True)
class FFCzBlob:
    """Serialized FFCz compression result.

    Version-1 wire layout (what :meth:`to_bytes` writes)::

        b"FFCZ" | u8 version | <ddBQQQQ> E, Delta, ndim, nb, ns, nf, npw
        | ndim * u64 shape | base | spat_edits | freq_edits | pointwise
        [| b"FFCP" pad-metadata section] [| b"FFCR" ROI bound section]
        [| b"FFCC" CRC section]

    :meth:`from_bytes` length-validates every section against the payload
    and raises ``ValueError`` on truncated or foreign bytes.  Blobs written
    before the magic was introduced (version 0) start directly with the
    ``<ddBQQQQ>`` header; they are sniffed by the absent magic and decode
    unchanged.  The optional trailing :class:`PadMeta` section (uneven
    sharded writers only) is sniffed the same way — by its ``FFCP`` marker
    at the end of the core sections — so pad-free v1 blobs parse unchanged
    in both directions.
    """

    base_blob: bytes
    spat_edits: EncodedEdits
    # Frequency edit stream.  New blobs store the rfft half-spectrum (its
    # ``half_spectrum`` format flag set); legacy blobs store the full
    # spectrum and decode through the ifftn branch of ``FFCz.decompress``.
    freq_edits: EncodedEdits
    E: float
    Delta_scalar: float  # scalar Delta, or nan when pointwise (stored in blob)
    # float32 Delta_k grid bytes, or None; half-spectrum layout iff
    # ``freq_edits.half_spectrum`` (legacy blobs stored the full grid)
    pointwise_delta: Optional[bytes]
    shape: tuple
    stats: Optional[FFCzStats] = None
    # Optional slab-decomposition provenance (uneven sharded writers only);
    # informational — see PadMeta.
    pad_meta: Optional[PadMeta] = None
    # Optional float32 per-point spatial bound grid (ROI mode, FFCR tail
    # section; field shape/order).  SEMANTIC — unlike pad_meta/crc it is the
    # spatial bound the edits were encoded against, so decode must consume
    # it and payload_bytes() keeps it.  None for uniform-E writers (their
    # blobs stay byte-identical to pre-ROI writers).
    roi_bound: Optional[bytes] = None
    # Write (and re-write) the optional FFCC per-section CRC32 tail.  Set by
    # the parser when the section is present, so decode -> re-encode stays
    # byte-stable in both directions; blobs without the tail (every pre-CRC
    # writer) stay byte-identical.
    crc: bool = False

    @staticmethod
    def from_plan(
        plan,
        base_blob: bytes,
        spat_edits: EncodedEdits,
        freq_edits: EncodedEdits,
        crc: bool,
        pad_meta: Optional[PadMeta] = None,
    ) -> "FFCzBlob":
        """The blob every writer assembles from its
        :class:`~repro.core.engine.FieldPlan`, base blob and edit streams."""
        return FFCzBlob(
            base_blob=base_blob,
            spat_edits=spat_edits,
            freq_edits=freq_edits,
            E=plan.E,
            Delta_scalar=plan.delta_scalar,
            pointwise_delta=plan.pointwise_bytes(),
            shape=plan.shape,
            pad_meta=pad_meta,
            roi_bound=plan.roi_bytes(),
            crc=crc,
        )

    def to_bytes(self) -> bytes:
        se = self.spat_edits.to_bytes()
        fe = self.freq_edits.to_bytes()
        pw = self.pointwise_delta or b""
        header = _MAGIC + struct.pack("<B", _WIRE_VERSION)
        header += struct.pack(
            _V0_HEADER,
            self.E,
            self.Delta_scalar,
            len(self.shape),
            len(self.base_blob),
            len(se),
            len(fe),
            len(pw),
        )
        header += struct.pack(f"<{len(self.shape)}Q", *self.shape)
        tail = self.pad_meta.to_bytes() if self.pad_meta is not None else b""
        if self.roi_bound is not None:
            tail += _ROI_MAGIC + struct.pack("<Q", len(self.roi_bound)) + self.roi_bound
        out = header + self.base_blob + se + fe + pw + tail
        if self.crc:
            crcs = [zlib.crc32(out)] + [zlib.crc32(s) for s in (self.base_blob, se, fe, pw)]
            out += _CRC_MAGIC + struct.pack("<B", len(crcs)) + struct.pack(f"<{len(crcs)}I", *crcs)
        return out

    def payload_bytes(self) -> bytes:
        """Blob bytes with the informational pad-metadata and CRC tails
        stripped — the unit of cross-backend byte parity for ``"bitwise"``
        shapes."""
        if self.pad_meta is None and not self.crc:
            return self.to_bytes()
        return dataclasses.replace(self, pad_meta=None, crc=False).to_bytes()

    @staticmethod
    def from_bytes(data: bytes) -> "FFCzBlob":
        try:
            if data[:4] == _MAGIC:
                if len(data) < 5:
                    raise BlobCorruptError("truncated FFCz blob: magic without version byte")
                version = data[4]
                if version != _WIRE_VERSION:
                    raise BlobCorruptError(f"unsupported FFCz blob version {version}")
                return FFCzBlob._parse(data, offset=5)
            # version-0 sniff: magic-less blobs start directly with the header
            return FFCzBlob._parse(data, offset=0)
        except FFCzError:
            raise
        except Exception as e:
            # untrusted bytes: struct/slice/decode failures all classify as
            # corruption, never an unstructured crash
            raise BlobCorruptError(f"corrupt FFCz blob: {type(e).__name__}: {e}", cause=e) from e

    @staticmethod
    def _parse(data: bytes, offset: int) -> "FFCzBlob":
        head = struct.calcsize(_V0_HEADER)
        if len(data) < offset + head:
            raise BlobCorruptError(
                f"truncated FFCz blob: {len(data)} bytes < {offset + head}-byte header"
            )
        E, Delta, ndim, nb, ns, nf, npw = struct.unpack_from(_V0_HEADER, data, offset)
        off = offset + head
        if ndim > 16:
            raise BlobCorruptError(f"not an FFCz blob: implausible rank {ndim}")
        if len(data) < off + 8 * ndim:
            raise BlobCorruptError("truncated FFCz blob: shape table cut off")
        shape = struct.unpack_from(f"<{ndim}Q", data, off)
        off += 8 * ndim
        expected = off + nb + ns + nf + npw
        if len(data) < expected:
            raise BlobCorruptError(
                f"corrupt FFCz blob: {len(data)} bytes, section table wants {expected}"
            )
        base = data[off : off + nb]
        se_raw = data[off + nb : off + nb + ns]
        fe_raw = data[off + nb + ns : off + nb + ns + nf]
        pw = data[off + nb + ns + nf : expected] if npw else None
        # optional tail sections, each sniffed by its marker: FFCP pad
        # metadata, then the FFCR ROI bound grid, then the FFCC integrity
        # section (always last, since its leading CRC covers every byte
        # before it); any other tail bytes are corruption.  v0 and tail-free
        # v1 blobs take none of these branches.
        pad_meta, roi_bound, has_crc, pos = None, None, False, expected
        if data[pos : pos + 4] == _PAD_MAGIC:
            pad_meta, pos = PadMeta._parse_at(data, pos)
        if data[pos : pos + 4] == _ROI_MAGIC:
            if len(data) < pos + 12:
                raise BlobCorruptError("corrupt FFCz blob: truncated ROI bound section")
            (n_roi,) = struct.unpack_from("<Q", data, pos + 4)
            n_expect = 4 * (int(np.prod(shape)) if shape else 1)
            if n_roi != n_expect:
                raise BlobCorruptError(
                    f"corrupt FFCz blob: ROI bound section is {n_roi} bytes, a "
                    f"float32 grid over shape {tuple(shape)} needs {n_expect}"
                )
            if len(data) < pos + 12 + n_roi:
                raise BlobCorruptError("corrupt FFCz blob: truncated ROI bound section")
            roi_bound = data[pos + 12 : pos + 12 + n_roi]
            pos += 12 + n_roi
        if data[pos : pos + 4] == _CRC_MAGIC:
            FFCzBlob._verify_crc(data, pos, (base, se_raw, fe_raw, pw or b""))
            # fixed-size tail: magic + count byte + 5 verified u32 CRCs
            has_crc, pos = True, pos + 4 + 1 + 4 * len(_CRC_SECTIONS)
        if pos != len(data):
            raise BlobCorruptError(
                "corrupt FFCz blob: trailing bytes are not a pad-metadata, "
                "ROI-bound, or CRC section"
            )
        se = EncodedEdits.from_bytes(se_raw)
        fe = EncodedEdits.from_bytes(fe_raw)
        return FFCzBlob(
            base_blob=base,
            spat_edits=se,
            freq_edits=fe,
            E=E,
            Delta_scalar=Delta,
            pointwise_delta=pw,
            shape=tuple(shape),
            pad_meta=pad_meta,
            roi_bound=roi_bound,
            crc=has_crc,
        )

    @staticmethod
    def _verify_crc(data: bytes, pos: int, sections: tuple) -> None:
        """Validate the FFCC tail at ``pos`` against the parsed sections.

        The leading CRC covers every byte before the tail (header included);
        the per-section CRCs localize a mismatch to the corrupt section for
        the error message.
        """
        tail_head = pos + 4 + 1
        if len(data) < tail_head:
            raise BlobCorruptError("corrupt FFCz blob: truncated CRC section")
        n = data[pos + 4]
        if n != len(_CRC_SECTIONS) or len(data) < tail_head + 4 * n:
            raise BlobCorruptError("corrupt FFCz blob: malformed CRC section")
        stored = struct.unpack_from(f"<{n}I", data, tail_head)
        actual = (zlib.crc32(data[:pos]),) + tuple(zlib.crc32(b) for b in sections)
        if stored == actual:
            return
        # All five must match: a mismatch confined to a stored per-section CRC
        # (leading CRC fine) still means the tail bytes were flipped.
        for name, s, a in zip(_CRC_SECTIONS[1:], stored[1:], actual[1:]):
            if s != a:
                raise BlobCorruptError(f"corrupt FFCz blob: CRC mismatch in {name} section")
        raise BlobCorruptError("corrupt FFCz blob: CRC mismatch in header section")

    def nbytes(self) -> int:
        return len(self.to_bytes())


# -- pencil envelope (FFSB) -------------------------------------------------
#
# One pencil-planned tensor: magic, version, <ddIB> E/Delta/block/ndim,
# ndim * u64 shape, <QQQ> section lengths, sections, trailing u32 CRC32 of
# every preceding byte.  A new wire format (no legacy writers), so the CRC
# is unconditional.

PENCIL_MAGIC = b"FFSB"
_PENCIL_VERSION = 1
PENCIL_HEADER = "<ddIB"


def encode_pencil_blob(shape, base_blob: bytes, se, fe, plan, block: int) -> bytes:
    """Assemble the ``FFSB`` envelope of one tensor from its
    :class:`~repro.core.engine.PencilPlan`, base blob and edit streams."""
    se_b, fe_b = se.to_bytes(), fe.to_bytes()
    out = PENCIL_MAGIC + struct.pack("<B", _PENCIL_VERSION)
    out += struct.pack(PENCIL_HEADER, plan.E, plan.Delta, block, len(shape))
    out += struct.pack(f"<{len(shape)}Q", *shape)
    out += struct.pack("<QQQ", len(base_blob), len(se_b), len(fe_b))
    out += base_blob + se_b + fe_b
    return out + struct.pack("<I", zlib.crc32(out))


def decode_pencil_blob(data: bytes, base: Any) -> np.ndarray:
    """Hardened decode of the pencil envelope (``FFSB``).

    Every malformation — bad magic/version, truncation, section overrun,
    CRC mismatch, codec garbage — raises :class:`BlobCorruptError`.
    """
    try:
        if data[:4] != PENCIL_MAGIC:
            raise BlobCorruptError("not an FFCz service pencil blob: bad magic")
        if len(data) < 9 or data[4] != _PENCIL_VERSION:
            raise BlobCorruptError(
                f"unsupported service pencil blob version {data[4] if len(data) > 4 else '?'}"
            )
        if len(data) < 4 + 1 + 4:
            raise BlobCorruptError("truncated service pencil blob")
        body, (crc,) = data[:-4], struct.unpack_from("<I", data, len(data) - 4)
        if zlib.crc32(body) != crc:
            raise BlobCorruptError("corrupt service pencil blob: CRC mismatch")
        off = 5
        E, Delta, block, ndim = struct.unpack_from(PENCIL_HEADER, body, off)
        off += struct.calcsize(PENCIL_HEADER)
        if ndim > 16:
            raise BlobCorruptError(f"corrupt service pencil blob: implausible rank {ndim}")
        shape = struct.unpack_from(f"<{ndim}Q", body, off)
        off += 8 * ndim
        nb, ns, nf = struct.unpack_from("<QQQ", body, off)
        off += struct.calcsize("<QQQ")
        if len(body) != off + nb + ns + nf:
            raise BlobCorruptError(
                f"corrupt service pencil blob: {len(body)} bytes, sections want {off + nb + ns + nf}"
            )
        base_blob = body[off : off + nb]
        se = EncodedEdits.from_bytes(body[off + nb : off + nb + ns])
        fe = EncodedEdits.from_bytes(body[off + nb + ns : off + nb + ns + nf])
        x_hat = np.asarray(base.decompress(base_blob), dtype=np.float32)
        spat = decode_edits(se, E)
        freq = decode_edits(fe, Delta)
        complete = spat + np.fft.irfft(freq, n=block, axis=-1)
        size = int(np.prod(shape)) if shape else 1
        x = x_hat.astype(np.float64).reshape(-1) + complete.reshape(-1)[:size]
        return x.reshape(shape).astype(np.float32)
    except FFCzError:
        raise
    except Exception as e:  # noqa: BLE001 - untrusted bytes
        raise BlobCorruptError(
            f"corrupt service pencil blob: {type(e).__name__}: {e}", cause=e
        ) from e


def _irfftn(a: np.ndarray, shape) -> np.ndarray:
    """numpy irfftn with explicit axes (required for odd last-axis sizes)."""
    return np.fft.irfftn(a, s=shape, axes=tuple(range(len(shape))))


class FFCz:
    """Spectrum-preserving codec wrapping an arbitrary base compressor.

    ``base`` must expose ``compress(x, E) -> bytes`` and
    ``decompress(blob) -> np.ndarray`` with a pointwise L-inf guarantee.
    ``engine`` defaults to the shared process-wide engine.

    Sharded whole fields: passing a
    :class:`repro.sharding.dist_fft.ShardedField` to :meth:`compress` runs
    the PLAN spectra and the EXECUTE POCS loop distributed (pencil-
    decomposed rfftn under ``shard_map`` — device HBM never holds the
    gathered field), producing a blob bitwise identical to compressing the
    gathered field on one device.  The base compressor and the edit encoder
    are host codecs by contract, so they stage through the field's host
    copy exactly as the single-device pipeline does.  The engine *backend*
    still only selects how pencil batches execute via ``engine.correct``.
    """

    def __init__(self, base: Any, config: FFCzConfig = FFCzConfig(), engine: Optional[CorrectionEngine] = None):
        self.base = base
        self.config = config
        self.engine = engine or default_engine()

    # -- compression ------------------------------------------------------

    def compress(self, x) -> FFCzBlob:
        cfg = self.config
        sharded = isinstance(x, ShardedField)
        x32 = x.to_host() if sharded else np.asarray(x, dtype=np.float32)

        plan = self.engine.plan_field(x if sharded else x32, cfg)
        base_blob, eps0 = base_residual(self.base, x32, plan.E_proj)
        if sharded:
            eps0 = ShardedField(
                eps0, x.mesh, x.axis_name, x.parity_requested, x.overlap_chunks
            )
        result = self.engine.execute_field(eps0, plan)
        se, fe = self.engine.encode_field(result, plan)

        # Provenance for uneven slab decompositions: record how the field was
        # padded/sharded at write time.  Optional (absent for single-device
        # and evenly divisible writes, keeping those blobs byte-identical to
        # pre-pad writers) and ignored by decompress — the edit streams are
        # always encoded at the true extents.
        pad_meta = None
        if sharded and x.padded_shape != x.shape:
            pad_meta = PadMeta(n_dev=x.n_dev, padded_shape=x.padded_shape)

        blob = FFCzBlob.from_plan(plan, base_blob, se, fe, crc=cfg.crc, pad_meta=pad_meta)

        stats = None
        if cfg.verify:
            stats = self.verify_stats(blob, x32, result, plan=plan)
        return dataclasses.replace(blob, stats=stats)

    def verify_stats(self, blob: FFCzBlob, x32: np.ndarray, result, plan=None) -> FFCzStats:
        """Decode ``blob`` back and measure both bound margins against ``x32``.

        Factored out of :meth:`compress` so the serving layer can verify a
        blob it assembled through the staged engine path (plan / execute /
        encode) without re-running compression; ``plan`` is recomputed when
        the caller no longer holds it (planning is deterministic).
        """
        if plan is None:
            plan = self.engine.plan_field(x32, self.config)
        x_final = self.decompress(blob)
        eps = x_final.astype(np.float64) - x32.astype(np.float64)
        # half-spectrum check is exhaustive: every full-spectrum component
        # shares |Re|/|Im| (and its Delta_k) with its conjugate image here
        d = np.fft.rfftn(eps)
        if blob.roi_bound is not None:
            # ROI mode: the margin is against the STORED per-point grid, so
            # a held bound means every region's own E_n held, not just the
            # global envelope
            grid64 = np.frombuffer(blob.roi_bound, dtype=np.float32).reshape(
                blob.shape
            ).astype(np.float64)
            spatial_margin = float(np.min(grid64 - np.abs(eps)))
        else:
            spatial_margin = float(plan.E - np.max(np.abs(eps)))
        freq_excess = np.maximum(np.abs(d.real), np.abs(d.imag)) - np.asarray(plan.Delta)
        frequency_margin = float(-np.max(freq_excess))
        pspec_shell_err = pspec_shell_ok = None
        cfg = self.config
        if cfg.verify_pspec and cfg.pspec_rel is not None:
            from repro.core.spectrum import shell_ratio_error

            pspec_shell_err = float(shell_ratio_error(x_final, x32))
            pspec_shell_ok = bool(pspec_shell_err <= cfg.pspec_rel)
        return FFCzStats(
            iterations=result.iterations,
            converged=result.converged,
            n_active_spatial=blob.spat_edits.n_active,
            n_active_frequency=blob.freq_edits.n_active,
            base_bytes=len(blob.base_blob),
            edit_bytes=blob.spat_edits.nbytes() + blob.freq_edits.nbytes(),
            spatial_margin=spatial_margin,
            frequency_margin=frequency_margin,
            final_violations=result.final_violations,
            pspec_shell_err=pspec_shell_err,
            pspec_shell_ok=pspec_shell_ok,
        )

    # -- decompression ----------------------------------------------------

    def decompress(self, blob: FFCzBlob) -> np.ndarray:
        try:
            return self._decompress(blob)
        except FFCzError:
            raise
        except Exception as e:
            # decode consumes untrusted bytes end to end: any failure past
            # structural validation (codec garbage that entropy-decodes to the
            # wrong element count, off-shape buffers) is still corruption
            raise BlobCorruptError(f"corrupt FFCz blob: {type(e).__name__}: {e}", cause=e) from e

    def _decompress(self, blob: FFCzBlob) -> np.ndarray:
        x_hat = np.asarray(self.base.decompress(blob.base_blob), dtype=np.float32)
        if x_hat.shape != tuple(blob.shape):
            raise BlobCorruptError(
                f"corrupt FFCz blob: base section decodes to shape {x_hat.shape}, "
                f"header says {tuple(blob.shape)}"
            )
        half = blob.freq_edits.half_spectrum
        if blob.pointwise_delta is not None:
            # pointwise Delta_k grid, stored in the blob (Observation 4 mode);
            # half-spectrum layout in rfft-era blobs, full grid in legacy ones
            dshape = rfft_shape(blob.shape) if half else blob.shape
            Delta = np.frombuffer(blob.pointwise_delta, dtype=np.float32).reshape(dshape)
        else:
            Delta = blob.Delta_scalar
        if blob.roi_bound is not None:
            # per-point E_n grid (ROI mode): the spatial stream was quantized
            # against the stored grid, so decode must use the same values
            E_dec = np.frombuffer(blob.roi_bound, dtype=np.float32).reshape(blob.shape)
        else:
            E_dec = blob.E
        spat = decode_edits(blob.spat_edits, E_dec)
        freq = decode_edits(blob.freq_edits, Delta)
        if half:
            freq_spatial = _irfftn(freq, blob.shape)
        else:
            # legacy full-spectrum blob (pre-rfft format flag)
            freq_spatial = np.fft.ifftn(freq).real
        complete = spat + freq_spatial  # complete spatial edits (§IV-B)
        return (x_hat.astype(np.float64) + complete).astype(np.float32)

    def decompress_sharded(
        self,
        blob: FFCzBlob,
        mesh=None,
        axis_name: str = "data",
        parity="auto",
        strict_bitwise: Optional[bool] = None,
    ) -> ShardedField:
        """Decode a blob to a field resident on the mesh (slab-sharded, axis 0).

        Decoding itself is host-bound: the blob sections are host bytes, and
        the complete-spatial-edits inverse must run in float64 for the stored
        dual-bound guarantees to verify exactly (the device path is float32).
        The reconstructed field is scattered straight to its slabs (uneven
        extents re-pad automatically), so the result is bitwise identical to
        :meth:`decompress` while landing device-resident for distributed
        consumers.

        ``parity`` defaults to ``"auto"`` here — the scatter runs no
        distributed FFT, so the power-of-two bitwise precondition is
        irrelevant to decoding (and blobs written from ``"bound"``-parity
        fields must stay decodable).  A blob's :class:`PadMeta` (if any) is
        informational and not consulted: the target decomposition comes from
        ``mesh``, which need not match the writer's.
        """
        x = self.decompress(blob)
        return ShardedField.shard(
            x, mesh, axis_name=axis_name, parity=parity, strict_bitwise=strict_bitwise
        )

    def roundtrip(self, x):
        blob = self.compress(x)
        return self.decompress(blob), blob
