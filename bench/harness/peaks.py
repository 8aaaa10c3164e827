"""Per-chip peaks, keyed by JAX's ``device_kind`` (``data/peaks.json``).

A device that is not in the table is an error: a share of a peak that
was guessed for another chip means nothing.
"""
from __future__ import annotations

import json
import os

TABLE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "data", "peaks.json")


class UnknownDevice(KeyError):
    """The peaks table has no entry for this device kind."""


def load(path: str = TABLE) -> dict:
    with open(path) as f:
        return json.load(f)


def for_kind(device_kind: str, path: str = TABLE) -> dict:
    table = load(path)
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path}; known: "
            f"{sorted(table)}")
    return table[device_kind]
