"""Host-to-device copies on the loader's thread (self time of `h2d.put`),
ms per snapshot."""
from bench.program_spans import ms_per_snapshot


def read(run):
    return ms_per_snapshot(run, "h2d.put")
