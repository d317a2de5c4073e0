"""Published peaks of each accelerator, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class UnknownDevice(LookupError):
    """The device is not in the peak table: no share of a peak can be read."""


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in {path.name} "
            f"(known: {sorted(table)})"
        )
    return table[device_kind]
