"""The program's retrieval spans in a traced run of a served cell.

On a served cell the program opens its spans on the query scheduler's
workers and the prefetch threads, never on the thread that holds the
harness's window span (``serve.window``, ``bench/paths/serve.py``
``SPANS[0]``), so what a reader wants is their busy time: on each host
line (thread), the union of the named spans' intervals (a span of the
set nested in another counts once), clipped to the window, summed over
the lines.  ``bench/program_spans.py`` does the same for the loader,
whose window span it names.

A trace without a window span or without any of the named spans reads
``None``, and so do the readers built on it.
"""
from __future__ import annotations

import os
import warnings

from bench.program_spans import newest_trace, program_names

WINDOW = "serve.window"


def reduce_lines(lines: list[list[tuple[str, float, float]]]) -> dict | None:
    """``lines``: per host line, ``(name, start, end)`` in nanoseconds.
    Returns the window and, per line, the events inside it clipped to it,
    or ``None`` without a window span."""
    win = next(((a, b) for evs in lines for n, a, b in evs if n == WINDOW),
               None)
    if win is None:
        return None
    lo, hi = win
    return {"window_s": (hi - lo) / 1e9,
            "lines": [[(n, max(a, lo), min(b, hi)) for n, a, b in evs
                       if n != WINDOW and min(b, hi) > max(a, lo)]
                      for evs in lines]}


def busy_s(reduced: dict | None, names) -> float | None:
    """Seconds of the window in which some span of ``names`` was open,
    per line, summed over the lines; ``None`` where none was."""
    if reduced is None:
        return None
    names = set(names)
    total, seen = 0.0, False
    for evs in reduced["lines"]:
        end = float("-inf")
        for a, b in sorted((a, b) for n, a, b in evs if n in names):
            seen = True
            if b > end:
                total += b - max(a, end)
                end = b
    return total / 1e9 if seen else None


def reduce_file(path: str, names) -> dict | None:
    """Reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``."""
    from jax.profiler import ProfileData
    keep = set(names) | {WINDOW}
    warnings.filterwarnings("ignore", "builtin type event_stats",
                            DeprecationWarning)
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events if e.name in keep]
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    return reduce_lines(lines)


_cache: dict[tuple, dict | None] = {}


def ms_per_point(run, *names: str) -> float | None:
    """Busy time of ``names`` in the traced window, ms per point answered
    in it."""
    path, known = newest_trace(), program_names()
    if run.trace is None or path is None or known is None \
            or not run.window["snapshots"]:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache[key] = reduce_file(path, known)
    secs = busy_s(_cache[key], names)
    return None if secs is None else 1e3 * secs / run.window["snapshots"]
