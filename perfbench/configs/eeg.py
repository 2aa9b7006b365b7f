"""EEG-like channels x time windows from a seed (the ``eeg`` configuration's data).

Per channel a random-walk drift, plus an alpha-band oscillation shared by
the channels with a per-channel phase, plus sensor noise: the structure of
the program's ``eeg_batch`` smoke data, at the dataset's 160 Hz.
"""

from __future__ import annotations

import numpy as np


def make(cfg: dict, size: str, index: int, seed: int) -> np.ndarray:
    """Window ``index`` of ``size`` blocks: ``(channels, size * block)`` float32."""
    channels, samples = int(cfg["channels"]), int(size) * int(cfg["block"])
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), int(size), int(index)]))
    t = np.arange(samples) / float(cfg["sample_rate_hz"])
    freq = rng.uniform(8.0, 12.0)
    phase = rng.uniform(0.0, 2 * np.pi, (channels, 1))
    x = (
        (rng.standard_normal((channels, samples)) * 0.3).cumsum(axis=1)
        + np.sin(2 * np.pi * freq * t[None, :] + phase)
        + 0.01 * rng.standard_normal((channels, samples))
    )
    return np.ascontiguousarray(x, np.float32)
