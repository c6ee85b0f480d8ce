"""Planning on the loader's thread: Steiner / singlepoint plans on the
skeleton and net event slices between interval points (self time of the
program's `retrieve.plan` and `slice.quads` spans), ms per snapshot."""
from bench.program_spans import ms_per_snapshot


def read(run):
    return ms_per_snapshot(run, "retrieve.plan", "slice.quads")
