"""The chip's published peaks, one table keyed by JAX's `device_kind`
(`perfbench/peaks.json`, with its source). A device that is not in the
table is an error: a roofline share against a guessed peak means
nothing."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str, path: str = PEAKS_FILE) -> dict:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r} in {path} "
            f"(known: {sorted(table)})")
    return table[device_kind]
