"""Payload decompression and deserialization on a served cell (busy time
of the program's `codec.decode` spans on every thread), ms per point
answered."""
from bench.serve_spans import ms_per_point


def read(run):
    return ms_per_point(run, "codec.decode")
