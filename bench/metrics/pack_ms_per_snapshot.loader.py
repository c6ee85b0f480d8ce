"""Index lists to packed bit planes and packed planes back to bool masks
on the loader's thread (self time of `host.pack` and `host.unpack`), ms
per snapshot."""
from bench.program_spans import ms_per_snapshot


def read(run):
    return ms_per_snapshot(run, "host.pack", "host.unpack")
