"""The parts of a cell, each found by name in a file of its own.

* a history generator: ``bench/generators/<name>.py``, whose
  ``generate(seed, **params)`` returns a ``bench.history.History``;
  a configuration names it under ``history.generator``;
* a store: ``bench/stores/<kind>.py``, whose ``make(spec, directory)``
  returns the program's ``KVStore``; a configuration names it under
  ``store.kind``;
* a path, the entry point a traffic mix drives: ``bench/paths/<path>.py``,
  with ``SPANS`` (the harness spans it opens, the window's first) and
  ``Driver(cell, traffic)``; a traffic mix names it under ``path``;
* a metric: ``bench/metrics/<name>.py``, whose ``read(run)`` returns the
  number or ``None``; ``BENCHMARK.json`` names it.

No table lists them: adding one is adding its file.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent


def find(kind: str, name: str) -> ModuleType:
    """The module ``bench/<kind>/<name>.py``, loaded once a process.
    A name with no file raises ``LookupError`` naming the file."""
    key = f"bench.{kind}.{name}"
    if key in sys.modules:
        return sys.modules[key]
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise LookupError(f"no {kind} file for {name!r}: "
                          f"{path.relative_to(BENCH.parent)} does not exist")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod


def store(spec: dict, directory: Path):
    """The store a ``{"kind": ..., **parameters}`` spec names; one that
    keeps files keeps them in ``directory``."""
    return find("stores", spec["kind"]).make(spec, directory)
