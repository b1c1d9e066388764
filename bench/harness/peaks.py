"""The table of device peaks (``bench/peaks.json``), keyed by jax's
``device_kind``.  A kind that is not in the table is an error."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str, path: Path = PEAKS) -> dict:
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"{path.name}; known: {sorted(table)}")
    return table[device_kind]
