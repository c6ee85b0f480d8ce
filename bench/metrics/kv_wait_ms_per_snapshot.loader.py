"""The loader's thread blocked on a store fetch: prefetch futures and
synchronous fetches (self time of the program's `kv.wait` span), ms per
snapshot."""
from bench.program_spans import ms_per_snapshot


def read(run):
    return ms_per_snapshot(run, "kv.wait")
