"""Device time of the fixpoint solvers' programs (those whose name holds
`fixpoint`, as the trace reduction keeps each program's time) in the
window, ms per analytic point answered in it."""
from bench.readers import ratio
from bench.serve_readers import program_seconds


def read(run):
    return ratio(program_seconds(run, "fixpoint"),
                 run.window["analytic_points"], 1e3)
