"""Bytes read device to host in the window: the `bytes` each `d2h.copy`
span carries (`runtime/staging.to_host`), MB per snapshot."""
from bench.program_spans import mb_per_snapshot


def read(run):
    return mb_per_snapshot(run, "d2h.copy")
