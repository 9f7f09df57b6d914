"""Published per-chip peaks, keyed by JAX's ``device_kind`` (``peaks.json``)."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def for_kind(kind: str) -> dict:
    """The peaks of one device kind; an unknown kind is an error, not a default."""
    devices = json.loads(TABLE.read_text())["devices"]
    if kind not in devices:
        raise KeyError(f"no peaks for device kind {kind!r} in {TABLE}; known: {sorted(devices)}")
    return devices[kind]
