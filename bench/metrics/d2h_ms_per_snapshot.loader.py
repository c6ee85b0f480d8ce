"""Device-to-host reads on the loader's thread (self time of `d2h.copy`,
which includes waiting for the program that produces the array), ms per
snapshot."""
from bench.program_spans import ms_per_snapshot


def read(run):
    return ms_per_snapshot(run, "d2h.copy")
