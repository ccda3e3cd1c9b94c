"""The chip's published peaks, keyed by jax's `device_kind`. The benchmark's
own table: no environment variable overrides it, and a kind that is not
listed is an error, never a default (a share of an unknown peak is noise)."""

from __future__ import annotations

import os
from typing import Dict

from .spec import load_json

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def for_kind(device_kind: str) -> Dict[str, float]:
    peaks = load_json(_TABLE)["peaks"]
    if device_kind not in peaks:
        raise KeyError(f"device_kind {device_kind!r} is not in benchmarks/lib/peaks.json ({sorted(peaks)})")
    return peaks[device_kind]
