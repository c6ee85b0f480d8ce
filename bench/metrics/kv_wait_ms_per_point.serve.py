"""Waits for store payloads on a served cell (busy time of the program's
`kv.wait` spans on every thread), ms per point answered."""
from bench.serve_spans import ms_per_point


def read(run):
    return ms_per_point(run, "kv.wait")
