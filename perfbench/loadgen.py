"""The one traffic generator: reads a mix's parameters, draws from the seed.

A traffic file (``perfbench/traffic/<name>.json``) is data only:

``{"loop": "closed", "in_flight": 3}``
    A closed loop: the client keeps ``in_flight`` requests submitted and
    not yet answered, and sends the next when one completes.

``{"loop": "open", "arrivals": "poisson", "rate_per_s": R, "sizes": {...}}``
    An open loop: requests are due on a schedule whatever the service does.
    ``arrivals`` is ``"poisson"`` (exponential gaps at mean rate ``R``) or
    ``"onoff"`` (Poisson at ``R * (on_s + off_s) / on_s`` for ``on_s``
    seconds, then nothing for ``off_s`` seconds: mean rate ``R``).

``sizes`` maps a request size (the configuration says what a size means,
e.g. blocks of samples) to its share of requests.  ``ramp_s`` is how long
the loop runs before the measured window opens.

Every seed draws the same multiset of gaps and sizes, in another order: the
gaps are the exponential distribution's quantiles at ``(i + 0.5) / M`` and
the sizes come in exact shares, both shuffled by the seed.  So two seeds
offer the same work over the same time, and differ only in its order.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float  # seconds after the schedule's start
    size: str  # key of the traffic's ``sizes``


def sizes_for(traffic: dict, count: int, rng: np.random.Generator) -> List[str]:
    """``count`` sizes in the traffic's exact shares, shuffled by ``rng``."""
    shares = traffic.get("sizes") or {"1": 1.0}
    keys = sorted(shares)
    total = float(sum(shares[k] for k in keys))
    out: List[str] = []
    for k in keys:
        out += [k] * int(round(count * shares[k] / total))
    while len(out) < count:  # rounding: top up with the largest share
        out.append(max(keys, key=lambda k: shares[k]))
    out = out[:count]
    rng.shuffle(out)
    return out


def schedule(traffic: dict, seed: int, horizon_s: float,
             rate_per_s: Optional[float] = None) -> List[Arrival]:
    """The open loop's arrivals over ``horizon_s`` seconds, from ``seed``.

    ``rate_per_s`` overrides the file's rate (used by the knee sweep only).
    """
    if traffic.get("loop") != "open":
        raise ValueError("only an open loop has a schedule")
    rate = float(rate_per_s if rate_per_s is not None else traffic["rate_per_s"])
    kind = traffic.get("arrivals", "poisson")
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x7AF1C]))
    if kind == "poisson":
        on_rate, on_s, off_s = rate, horizon_s, 0.0
    elif kind == "onoff":
        on_s, off_s = float(traffic["on_s"]), float(traffic["off_s"])
        on_rate = rate * (on_s + off_s) / on_s
    else:
        raise ValueError(f"unknown arrivals {kind!r}")
    on_total = horizon_s * (on_s / (on_s + off_s))
    count = max(1, int(math.ceil(on_rate * on_total)))
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / on_rate
    rng.shuffle(gaps)
    on_times = np.cumsum(gaps) - gaps[0]
    if off_s > 0:  # map time spent "on" to wall time with the off periods
        on_times = on_times + np.floor(on_times / on_s) * off_s
    sizes = sizes_for(traffic, count, rng)
    return [Arrival(float(t), s) for t, s in zip(on_times, sizes) if t < horizon_s]
