"""Published peaks of each accelerator, keyed by JAX's ``device_kind``
(``peaks.json``, which names its source).  A device that is not in the
table is an error, never a default."""

from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def lookup(device_kind: str, path: str = PATH) -> dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {os.path.basename(path)}; known: {sorted(table)}")
    return table[device_kind]
