"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

The table is ``peaks.json`` beside this file; each entry names its source.
A device that is not in the table is an error, never a default: a roofline
share against a guessed peak is a number about nothing.
"""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


def lookup(device_kind: str, table: Path = TABLE) -> dict:
    """The peak entry for ``device_kind`` (as ``jax.devices()[0].device_kind``
    reports it); raises ``KeyError`` for a device the table does not hold."""
    peaks = json.loads(Path(table).read_text())
    if device_kind not in peaks:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"the table holds {sorted(peaks)}"
        )
    return peaks[device_kind]
