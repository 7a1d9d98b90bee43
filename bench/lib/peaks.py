"""Peak rates of each accelerator, keyed by JAX's ``device_kind``.

The table lives in ``peaks.json`` beside this file, with its source.  A
device that is not in the table is an error, never a default."""
from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, path: str = _PATH) -> dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device_kind {device_kind!r}; "
                            f"known: {sorted(table)}")
    return dict(table[device_kind])
