"""The chip's published peaks, from ``peaks.json`` beside this file."""

from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """The peaks entry whose key occurs in ``device_kind``; an unknown
    device raises (a number over a guessed peak would be worse than none)."""
    with open(_TABLE) as f:
        chips = json.load(f)["chips"]
    for key, entry in chips.items():
        if key.lower() in device_kind.lower():
            return entry
    raise KeyError(f"no peaks known for device_kind {device_kind!r}: add it "
                   f"to benchmarks/peaks.json with its source")
