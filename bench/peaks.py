"""The chip's published peaks, keyed by JAX's ``device_kind``."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of this kind. A kind not in the table is an error."""
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]
