"""The program's own spans in a traced run, thread by thread.

The program opens its spans (``repro.runtime.spans.NAMES``) on whichever
thread does the work: the loader's thread, and the ``kv-prefetch``
workers that fetch and decode.  This reduction reads the run's profiler
trace (the ``.xplane.pb`` that ``bench/run.py`` writes under
``bench/.run/trace``) and keeps each host event's line, which is its
thread:

* the window thread is the line that holds the harness's
  ``loader.window``;
* ``spans[name]``: ``self_s``, on the window thread, the span's time less
  the time its child spans on that thread cover, clipped to the window;
  ``busy_s``, its summed time on every other thread, clipped to the
  window; ``count``, its events in the window on any thread; ``bytes``,
  the ``bytes`` stat of its events that start in the window (the
  program's transfer counts, which ``h2d.put`` and ``d2h.copy`` carry);
* ``idle_gaps``: the device's idle time by the innermost span, harness or
  program, open on the window thread at each moment of it.  With only
  the harness's spans in a trace it is ``bench/trace.py``'s idle-gap
  breakdown.

A trace without the program's spans (a program that has none) reduces to
``None``, and the readers built on it return ``None``.

    python3 -m bench.program_spans     # the newest traced run, as JSON
"""
from __future__ import annotations

import glob
import os
import sys
import warnings
from collections import defaultdict
from pathlib import Path

from bench.trace import DEVICE_LINES, gaps

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / "bench" / ".run" / "trace"
# the harness's own spans (bench/load.py); the window span comes first
HARNESS = ("loader.window", "loader.next", "consumer.step")
OUTSIDE = "outside harness spans"


def program_names() -> tuple[str, ...] | None:
    try:
        from repro.runtime.spans import NAMES
    except ImportError:
        return None
    return tuple(NAMES)


def innermost_segments(events) -> list[tuple[float, float, str]]:
    """Properly nested ``(start, end, name)`` spans of one thread, cut into
    consecutive pieces labelled by the innermost span open over each."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, float, str]] = []
    cur = None

    def close_until(t: float) -> None:
        nonlocal cur
        while stack and stack[-1][1] <= t:
            _, b, name = stack.pop()
            if b > cur:
                out.append((cur, b, name))
                cur = b

    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        if cur is not None:
            close_until(a)
        if stack and a > cur:
            out.append((cur, a, stack[-1][2]))
        if stack:
            b = min(b, stack[-1][1])      # clock rounding: stay nested
        cur = a
        stack.append((a, b, name))
    if stack:
        close_until(float("inf"))
    return out


def _label_gaps(gap_list, segs, idle: dict) -> None:
    """Adds each gap's time to the label of the segment over it (sorted
    inputs, one merge pass)."""
    j = 0
    for a, b in gap_list:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        t, k = a, j
        while t < b:
            if k < len(segs) and segs[k][0] < b:
                s0, s1, name = segs[k]
                if s0 > t:
                    idle[OUTSIDE] += min(s0, b) - t
                    t = min(s0, b)
                    continue
                idle[name] += min(s1, b) - t
                t = min(s1, b)
                k += 1
            else:
                idle[OUTSIDE] += b - t
                t = b


def reduce_lines(device: dict[str, list[tuple[str, str, float, float]]],
                 lines: list[list[tuple[str, float, float, dict]]],
                 names, top: int = 12) -> dict | None:
    """The reduction over plain event lists, times in nanoseconds.

    ``device`` as ``bench.trace.reduce_events`` takes it; ``lines`` holds
    one list of ``(name, start, dur, stats)`` per host line (thread).
    Returns ``None`` without a window span or without any event named in
    ``names``."""
    names = set(names)
    win = next((i for i, evs in enumerate(lines)
                for n, *_ in evs if n == HARNESS[0]), None)
    if win is None or not any(n in names for evs in lines for n, *_ in evs):
        return None
    lo, hi = next((s, s + d) for n, s, d, _ in lines[win] if n == HARNESS[0])
    spans = {n: {"self_s": 0.0, "busy_s": 0.0, "count": 0, "bytes": 0}
             for n in names}
    segs = []
    for i, evs in enumerate(lines):
        for n, s, d, stats in evs:
            if n not in names or s + d <= lo or s >= hi:
                continue
            sp = spans[n]
            sp["count"] += 1
            if lo <= s:
                sp["bytes"] += int(stats.get("bytes", 0))
            if i != win:
                sp["busy_s"] += (min(s + d, hi) - max(s, lo)) / 1e9
        if i == win:
            segs = innermost_segments(
                [(s, s + d, n) for n, s, d, _ in evs
                 if n in names or n in HARNESS])
    for a, b, n in segs:
        if n in spans and b > lo and a < hi:
            spans[n]["self_s"] += (min(b, hi) - max(a, lo)) / 1e9
    idle: dict[str, float] = defaultdict(float)
    segs = [(a, b, n) for a, b, n in segs if b > lo and a < hi]
    for events in device.values():
        ivs = [(max(s, lo), min(s + d, hi)) for line, _, s, d in events
               if line in DEVICE_LINES and min(s + d, hi) > max(s, lo)]
        _label_gaps(gaps(ivs, lo, hi), segs, idle)
    n_dev = max(len(device), 1)
    return {
        "window_s": (hi - lo) / 1e9,
        "spans": spans,
        "idle_gaps": sorted(([k, v / n_dev / 1e9] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top]}


def reduce_file(path: str, names) -> dict | None:
    """Reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``."""
    from jax.profiler import ProfileData
    keep = set(names) | set(HARNESS)
    device: dict[str, list] = {}
    lines: list[list] = []
    # reading an event's stats warns that its type names no module
    warnings.filterwarnings("ignore", "builtin type event_stats",
                            DeprecationWarning)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            device[plane.name] = [
                (line.name, e.name, e.start_ns, e.duration_ns)
                for line in plane.lines if line.name in DEVICE_LINES
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.append([(e.name, e.start_ns, e.duration_ns,
                               dict(e.stats) if e.name in names else {})
                              for e in line.events if e.name in keep])
    return reduce_lines(device, lines, names)


def newest_trace(directory: Path = TRACE_DIR) -> str | None:
    files = glob.glob(str(directory / "plugins/profile/*/*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


_cache: dict[tuple, dict | None] = {}


def of_run(run) -> dict | None:
    """The reduction of a traced run's trace, read once per run."""
    names = program_names()
    path = newest_trace()
    if run.trace is None or names is None or path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache[key] = reduce_file(path, names)
    return _cache[key]


def per_snapshot(run, parts, scale: float) -> float | None:
    """``scale`` × the sum of ``(span, field)`` over the run's snapshots
    stepped; ``None`` where the run's trace has none of the spans."""
    r = of_run(run)
    if r is None or not run.window["snapshots"]:
        return None
    spans = r["spans"]
    if not any(spans.get(n, {}).get("count") for n, _ in parts):
        return None
    total = sum(spans[n][field] for n, field in parts if n in spans)
    return scale * total / run.window["snapshots"]


def ms_per_snapshot(run, *names: str) -> float | None:
    """Window-thread self time of ``names``, in ms per snapshot."""
    return per_snapshot(run, [(n, "self_s") for n in names], 1e3)


def mb_per_snapshot(run, name: str) -> float | None:
    """Bytes carried by ``name``'s events, in MB per snapshot."""
    return per_snapshot(run, [(name, "bytes")], 1e-6)


if __name__ == "__main__":
    import json
    sys.path.insert(0, str(ROOT / "src"))
    path = sys.argv[1] if len(sys.argv) > 1 else newest_trace()
    names = program_names()
    print(json.dumps(None if path is None or names is None
                     else reduce_file(path, names)))
