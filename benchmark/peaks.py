"""The chip's published peaks, keyed by JAX's ``device_kind``.

A kind that is not in ``peaks.json`` is an error: a utilization over a
guessed peak would be a number with no meaning.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    kinds = json.loads(PEAKS_FILE.read_text())["kinds"]
    if device_kind not in kinds:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
            f"known: {sorted(kinds)}"
        )
    return kinds[device_kind]
