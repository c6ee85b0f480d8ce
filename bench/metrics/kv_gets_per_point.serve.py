"""KV gets over the window (the store's counter) per point answered in
it, on a served cell: multipoint retrieval and each evolve document's
first snapshot and slices."""
from bench.readers import ratio


def read(run):
    return ratio(run.delta("kv_gets"), run.window["snapshots"])
