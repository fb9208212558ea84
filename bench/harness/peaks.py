"""Published peaks of one chip, keyed by JAX's ``device_kind``.  A device
that is not in ``peaks.json`` is an error, never a default."""

from __future__ import annotations

import json
import pathlib

_TABLE = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    table = json.loads(_TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
