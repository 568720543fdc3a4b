"""The chip's published peaks, from ``bench/peaks.json`` by device kind."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> Dict:
    """The row of ``device_kind`` (as JAX reports it).  A kind that is
    not in the table is an error: there is no default peak."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name} (have {sorted(table)})")
    return table[device_kind]
