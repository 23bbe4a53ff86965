"""The chip's published peaks, keyed by ``device_kind`` (``peaks.json``).
A device that is not in the table is an error, never a default."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(PATH) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PATH}; have {sorted(table)}")
    return table[device_kind]
