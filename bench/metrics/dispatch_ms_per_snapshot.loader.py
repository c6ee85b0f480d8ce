"""Host time of the kernel wrappers on the loader's thread: padding,
segment_sum's edge bucketing, dispatch (self time of `kernel.dispatch`),
ms per snapshot."""
from bench.program_spans import ms_per_snapshot


def read(run):
    return ms_per_snapshot(run, "kernel.dispatch")
