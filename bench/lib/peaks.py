"""Published peaks of each accelerator, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os

TABLE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The row for ``device_kind``; a kind not in the table is an error."""
    with open(TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {TABLE}; known: {sorted(table)}")
    return table[device_kind]
