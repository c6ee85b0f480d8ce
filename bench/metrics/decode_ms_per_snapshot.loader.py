"""Payload decompression and deserialization on every thread: self time
of `codec.decode` on the loader's thread plus its time on the prefetch
workers, ms per snapshot."""
from bench.program_spans import per_snapshot


def read(run):
    return per_snapshot(run, [("codec.decode", "self_s"),
                              ("codec.decode", "busy_s")], 1e3)
