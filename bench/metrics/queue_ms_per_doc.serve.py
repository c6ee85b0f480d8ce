"""Scheduler queue wait, enqueue to dispatch (the envelopes' `queue_s`),
ms per document answered in the window."""
from bench.readers import ratio


def read(run):
    return ratio(run.window["stats"].get("queue_s"), run.window["docs"], 1e3)
