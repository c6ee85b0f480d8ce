"""Planning on a served cell: Steiner / singlepoint plans and net event
slices between evolve points (busy time of the program's `retrieve.plan`
and `slice.quads` spans on every thread), ms per point answered."""
from bench.serve_spans import ms_per_point


def read(run):
    return ms_per_point(run, "retrieve.plan", "slice.quads")
