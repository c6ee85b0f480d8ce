"""Assembly of each batch's feature, edge-mask and label arrays on the
loader's thread (self time of `loader.assemble`), ms per snapshot."""
from bench.program_spans import ms_per_snapshot


def read(run):
    return ms_per_snapshot(run, "loader.assemble")
