"""Work of one POCS iteration, counted from the field shape alone.

One iteration of the paper's Alg. 1 on a real field of ``n`` points is a
real-to-complex transform, an f-cube clip with the violation count on the
half-spectrum, a complex-to-real transform and an s-cube clip.  The count
does not depend on which transform implementation (``fft_impl``) runs.

Operations (``flops``): ``2.5 n log2 n`` for each real transform (half the
``5 n log2 n`` of a complex one, the usual count), a clip of each part of
each half-spectrum value (2 per part, 4 per complex value), one compare per
part for the violation count (2 per complex value), and a clip of each
spatial value (2 per value).

Bytes (``bytes``): a lower bound that no implementation can beat.  Each
iteration changes the whole spatial state, which does not fit in on-chip
memory at the sizes measured, so it is read and written once; one edit
accumulator is read and written once too.  The second accumulator can be
derived from the state, the initial error and the first, so it is not
counted.  That is 4 transfers of ``4 n`` bytes (float32): ``16 n``.  A
kernel that moves more than this is slower than the bound, never faster,
so the roofline share cannot read over 100% however the transforms are
fused.
"""

from __future__ import annotations

import math
from typing import Sequence


def pocs_iteration(shape: Sequence[int]) -> dict:
    """``{"flops": ..., "bytes": ...}`` of one whole-field POCS iteration."""
    if not shape or any(int(s) < 1 for s in shape):
        raise ValueError(f"need a non-empty shape of positive extents, got {shape!r}")
    n = math.prod(int(s) for s in shape)
    half = math.prod(int(s) for s in shape[:-1]) * (int(shape[-1]) // 2 + 1)
    transforms = 2 * 2.5 * n * math.log2(n) if n > 1 else 0.0
    flops = transforms + 4 * half + 2 * half + 2 * n
    return {"flops": float(flops), "bytes": float(16 * n)}


def roofline_seconds(work: dict, peaks: dict) -> dict:
    """Least time the chip could take for ``work``, and which bound sets it."""
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(t_flops, t_bytes),
        "bound": "bytes" if t_bytes >= t_flops else "flops",
    }
