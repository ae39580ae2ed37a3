"""Finds the harness's parts by the names ``BENCHMARK.json`` and the data
files give them: ``bench/<kind>/<name>.py``, where ``kind`` is
``generators``, ``loops`` or ``metrics``."""

from __future__ import annotations

import importlib.util
import os
import re

BENCH = os.path.dirname(os.path.abspath(__file__))
_loaded: dict = {}


def module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded once."""
    key = (kind, name)
    if key not in _loaded:
        path = os.path.join(BENCH, kind, f"{name}.py")
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no {kind} module named {name!r} "
                                    f"({os.path.relpath(path, BENCH)})")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{re.sub(r'[^0-9A-Za-z_]', '_', name)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[key] = mod
    return _loaded[key]
