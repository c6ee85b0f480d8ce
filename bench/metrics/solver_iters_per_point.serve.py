"""Fixpoint iterations per analytic point answered in the window, from the
envelopes' `engine_stats.solver_iters`."""
from bench.readers import ratio


def read(run):
    return ratio(run.window.get("solver_iters"),
                 run.window["analytic_points"])
