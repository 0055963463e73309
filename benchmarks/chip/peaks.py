"""The chip's published peaks, one table keyed by JAX's ``device_kind``.

A device that is not in ``peaks.json`` is an error, never a default: a
share of a guessed peak says nothing."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = json.loads((Path(__file__).resolve().parent / "peaks.json")
                   .read_text())


def peaks(device_kind: str) -> dict:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {', '.join(TABLE)}") from None
