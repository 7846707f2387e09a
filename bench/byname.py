"""A benchmark file's module, loaded by its path: metric readers, model
families and kernel costs are files that the harness finds by name, so a
later change adds them as files alone."""
from __future__ import annotations

import functools
import importlib.util
import os
import re


def module(path: str):
    """The module of the file at ``path``, loaded once per process (and
    again after the file changes)."""
    path = os.path.realpath(path)
    return _load(path, os.stat(path).st_mtime_ns)


def sibling(file: str, name: str):
    """The module ``<name>.py`` in the directory of ``file``."""
    return module(os.path.join(os.path.dirname(file), f"{name}.py"))


@functools.lru_cache(maxsize=None)
def _load(path: str, mtime_ns: int):
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", path), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
