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


def keeps_recon(base) -> bool:
    """Whether ``base`` hands back the reconstruction it quantises against
    (a ``compress_with_recon`` method), so the base pass needs no decode."""
    return callable(getattr(base, "compress_with_recon", None))


def base_residual(base, x32: np.ndarray, E: float):
    """The base pass of every FFCz writer: ``(blob, eps0)``.

    ``blob = base.compress(x32, E)``; ``eps0 = float32(x̂) - x32`` is the
    base codec's error, the field the correction starts from.  ``x̂`` is the
    reconstruction the codec kept (:func:`keeps_recon`), which equals
    ``decompress(blob)`` bit for bit, else that decode.
    """
    if keeps_recon(base):
        blob, recon = base.compress_with_recon(x32, E)
        eps0 = recon.astype(np.float32)
    else:
        blob = base.compress(x32, E)
        eps0 = np.array(base.decompress(blob), dtype=np.float32)
    eps0 -= x32
    return blob, eps0


__all__ = [
    "SZLikeCompressor",
    "ZFPLikeCompressor",
    "SperrLikeCompressor",
    "IdentityCompressor",
    "base_residual",
    "keeps_recon",
    "get_compressor",
]
