"""The program's in-memory store (``MemKV``): a history that fits in the
host's memory.  ``{"kind": "mem"}``"""
from __future__ import annotations


def make(spec: dict, directory):
    from repro.storage.kv import MemKV
    return MemKV()
