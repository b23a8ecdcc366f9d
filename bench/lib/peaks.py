"""Published peaks of the chip, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


class UnknownDevice(KeyError):
    """The device is not in ``peaks.json``: a share of its peak has no base."""


def peaks_for(device_kind: str, path: pathlib.Path = PEAKS_FILE) -> dict:
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r} in {path.name}; known: "
                            f"{sorted(table)}")
    return table[device_kind]
