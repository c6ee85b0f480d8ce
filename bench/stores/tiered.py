"""The program's byte-budgeted hot tier in memory over a cold store
(``TieredKV``): ``{"kind": "tiered", "hot_bytes": n, "cold": <store spec>}``,
the cold store built from its own spec, in the same ``directory``."""
from __future__ import annotations


def make(spec: dict, directory):
    from bench import parts
    from repro.storage.kv import TieredKV
    return TieredKV(cold=parts.store(spec["cold"], directory),
                    hot_bytes=int(spec["hot_bytes"]))
