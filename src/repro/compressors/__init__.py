"""Error-bounded base compressors, reimplemented in JAX/numpy (paper §V-A).

All compressors satisfy the pointwise contract ``|decompress(compress(x, E)) -
x| <= E`` and are pluggable into :class:`repro.core.ffcz.FFCz`.
"""

import numpy as np

from repro.compressors.identity import IdentityCompressor
from repro.compressors.szlike import SZLikeCompressor
from repro.compressors.zfplike import SperrLikeCompressor, ZFPLikeCompressor

_REGISTRY = {
    "szlike": SZLikeCompressor,
    "zfplike": ZFPLikeCompressor,
    "sperrlike": SperrLikeCompressor,
    "identity": IdentityCompressor,
}


def get_compressor(name: str, **kwargs):
    """Instantiate a registered base compressor by name."""
    try:
        return _REGISTRY[name](**kwargs)
    except KeyError:
        raise ValueError(f"unknown base compressor {name!r}; have {sorted(_REGISTRY)}") from None


def base_residual(base, x32: np.ndarray, E: float):
    """The base pass of every FFCz writer: ``(blob, eps0)``.

    ``blob = base.compress(x32, E)``; ``eps0 = float32(decompress(blob)) -
    x32`` is the base codec's error, the field the correction starts from.
    """
    blob = base.compress(x32, E)
    return blob, np.asarray(base.decompress(blob), dtype=np.float32) - x32


__all__ = [
    "SZLikeCompressor",
    "ZFPLikeCompressor",
    "SperrLikeCompressor",
    "IdentityCompressor",
    "base_residual",
    "get_compressor",
]
