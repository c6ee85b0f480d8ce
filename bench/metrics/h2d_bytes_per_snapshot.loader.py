"""Bytes copied host to device in the window: the `bytes` each `h2d.put`
span carries (`runtime/staging.to_device`), MB per snapshot."""
from bench.program_spans import mb_per_snapshot


def read(run):
    return mb_per_snapshot(run, "h2d.put")
