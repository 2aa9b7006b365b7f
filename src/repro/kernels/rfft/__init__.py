"""Fused rFFT kernel suite: pack-trick C2R/R2C + projection epilogues.

The POCS hot loop spends its time in the inverse/forward real transforms
that bracket each projection pair.  This package provides the two faster
``fft_impl`` rungs behind the engine's selector (docs/architecture.md):

  ``packed``   pack-trick transforms (:mod:`repro.kernels.rfft.ops`): an
               N-point real transform rides an N/2-point complex FFT via
               twiddle recombination (``twiddle_plan``), restricted to
               even last axes (``supports_packed``), bitwise-gated against
               :mod:`repro.kernels.rfft.ref`.
  ``pallas``   the packed transform with the POCS projection epilogue
               fused into a Pallas kernel (:mod:`repro.kernels.rfft.kernel`):
               ``unpack_sclip_fused`` fuses C2R unpacking with the s-cube
               clip, ``fwd_epilogue_fused`` fuses R2C packing with the
               f-cube projection — eliminating one HBM round trip per loop
               iteration.  Compiles via Mosaic on TPU; interpret mode
               elsewhere.

Both impls produce the same per-block program across the local / batched /
sharded backends, and both accept the temporal warm-start state
(docs/streaming.md) unchanged — the warm spectrum enters as loop state, not
as a transform input.
"""

from repro.kernels.rfft.ops import (
    fwd_epilogue_fused,
    mirror_half_spectrum,
    packed_irfft,
    packed_irfftn,
    packed_rfftn,
    supports_packed,
    twiddle_plan,
    unpack_sclip_fused,
)

__all__ = [
    "fwd_epilogue_fused",
    "mirror_half_spectrum",
    "packed_irfft",
    "packed_irfftn",
    "packed_rfftn",
    "supports_packed",
    "twiddle_plan",
    "unpack_sclip_fused",
]
