"""Host advance of evolve documents from point to point (the envelopes'
`advance_s`, the `evolve.advance` spans' time), ms per analytic point
answered in the window."""
from bench.readers import ratio


def read(run):
    return ratio(run.window["stats"].get("advance_s"),
                 run.window["analytic_points"], 1e3)
