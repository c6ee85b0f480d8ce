"""Rendering and encoding of the reply (the envelopes' `reply_s`, the
`reply.render` span's time), ms per document answered in the window."""
from bench.readers import ratio


def read(run):
    return ratio(run.window["stats"].get("reply_s"), run.window["docs"], 1e3)
