"""Published per-chip peaks, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> dict:
    table = json.loads(_TABLE.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in {_TABLE.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]
