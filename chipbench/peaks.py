"""The table of per-chip peaks, keyed by ``device_kind`` (``peaks.json``).

A device that is not in the table is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

TABLE: Dict[str, Dict[str, float]] = json.loads(
    (Path(__file__).with_name("peaks.json")).read_text()
)


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}; known: {sorted(TABLE)}"
        ) from None
