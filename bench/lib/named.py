"""Modules found by name: `bench/<kind>/<name>.py`.

A configuration names its data proxy (`proxies/`) and its plain
reference (`references/`), a traffic mix names its op (`ops/`), and a
metric's file may name a shared reader (`readers/`). A later cell adds
such a module as a file of its own; nothing here lists them.
"""
from __future__ import annotations

import functools
import importlib.util
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=None)
def load(path: str):
    """The module in the file at `path`, loaded once."""
    stem = os.path.relpath(path, BENCH_DIR)[:-len(".py")]
    spec = importlib.util.spec_from_file_location(
        "bench_" + stem.replace(os.sep, "_").replace(".", "_"), path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def module(kind: str, name: str):
    """`bench/<kind>/<name>.py`."""
    return load(os.path.join(BENCH_DIR, kind, name + ".py"))
