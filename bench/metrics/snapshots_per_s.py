"""Snapshots in loader batches on which the consumer finished a step
within the window, per second."""


def read(run):
    return run.window["snapshots"] / run.seconds
