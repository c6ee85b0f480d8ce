"""KV gets over the window per snapshot the consumer stepped on."""
from bench.readers import ratio


def read(run):
    return ratio(run.delta("kv_gets"), run.window["snapshots"])
