"""Modules the harness finds by name, one file each, under a directory of
``bench/``: per-layer metric readers (``metrics/<name>.py``), traffic
drivers (``drivers/<kind>.py``) and data generators
(``generators/<name>.py``).  Nothing lists them: a new one is a new file.
"""

from __future__ import annotations

import importlib.util
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


def path(kind: str, name: str) -> str:
    return os.path.join(BENCH, kind, name + ".py")


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded from its file."""
    file = path(kind, name)
    if not os.path.exists(file):
        raise FileNotFoundError(f"no {kind} named {name!r}: {file} does not exist")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", file)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
