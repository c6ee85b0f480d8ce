"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
benchmark reports.

* The window is the harness's own window span, the first of the spans
  the cell's path opens (``loader.window`` for the loader), on the
  host's clock, which the device planes share.
* Device busy time is the union of the intervals in which an operation
  or a program ran on a device (lines ``XLA Ops`` and ``XLA Modules`` of
  each ``/device:TPU:n`` plane), clipped to the window and averaged over
  the devices.
* Kernels are found by the HLO instruction name of each custom call
  (``delta_apply_chain_batched_pallas``, ``segment_sum_bucketed``, ...),
  with the count of calls of each operand signature, from which
  ``bench/costs.py`` counts the bytes the calls need.  A kernel's time
  is that of the programs that ran it (``XLA Modules``): XLA stages a
  kernel's operands into on-chip memory before the custom call starts,
  so the call's own span leaves out the moves from HBM that its bytes
  need.  The call's own time is kept beside it, as ``kernel_seconds``.
* ``breakdown``: the programs that took most device time, and the idle
  time of the device by the innermost harness span open on the host at
  each moment of it.
"""
from __future__ import annotations

import bisect
import re
from collections import Counter, defaultdict

DEVICE_LINES = ("XLA Ops", "XLA Modules")
_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")


def union_length(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def innermost(spans: list[tuple[float, float, str]], t: float) -> str:
    best = None
    for a, b, name in spans:
        if a <= t <= b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "outside harness spans"


def reduce_events(device: dict[str, list[tuple[str, str, float, float]]],
                  host: list[tuple[str, float, float]], window: str,
                  top: int = 10) -> dict | None:
    """The reduction over plain event lists, times in nanoseconds.

    ``device`` maps a device plane to its ``(line, name, start, dur)``
    events; ``host`` lists ``(name, start, dur)`` harness spans, of which
    those named ``window`` mark the window.  Returns ``None`` when no
    window span is present."""
    wins = [(s, s + d) for n, s, d in host if n == window]
    if not wins:
        return None
    lo, hi = min(a for a, _ in wins), max(b for _, b in wins)
    spans = [(s, s + d, n) for n, s, d in host]
    busy_total, idle = 0.0, defaultdict(float)
    kernels: dict[str, dict] = {}
    modules: dict[str, float] = defaultdict(float)
    for events in device.values():
        ivs, progs, calls = [], [], []
        for line, name, s, d in events:
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            ivs.append((a, b))
            if line == "XLA Modules":
                modules[_MODULE.match(name).group(1)] += (b - a) / 1e9
                progs.append((s, d))
            elif "custom-call(" in name and lo <= s:
                calls.append((name, s, d))
        progs.sort()
        starts = [p[0] for p in progs]
        for name, s, d in calls:
            m = _OP.match(name)
            k = kernels.setdefault(m.group(1) if m else name[:64],
                                   {"seconds": 0.0, "kernel_seconds": 0.0,
                                    "calls": 0, "shapes": Counter(),
                                    "_progs": set()})
            k["kernel_seconds"] += d / 1e9
            k["shapes"][name.split("custom_call_target=", 1)[0]] += 1
            k["calls"] += 1
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s + d <= progs[i][0] + progs[i][1]:
                if progs[i] not in k["_progs"]:
                    k["_progs"].add(progs[i])
                    k["seconds"] += progs[i][1] / 1e9
            else:
                k["seconds"] += d / 1e9
        busy_total += union_length(ivs)
        for a, b in gaps(ivs, lo, hi):
            cuts = sorted({a, b} | {x for s0, s1, _ in spans
                                    for x in (s0, s1) if a < x < b})
            for c0, c1 in zip(cuts, cuts[1:]):
                idle[innermost(spans, (c0 + c1) / 2)] += (c1 - c0) / 1e9
    for k in kernels.values():
        del k["_progs"]
    n_dev = max(len(device), 1)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n_dev / 1e9,
        "kernels": kernels,
        "breakdown": {
            "device_ops": sorted(([k, v] for k, v in modules.items()),
                                 key=lambda kv: -kv[1])[:top],
            "idle_gaps": sorted(([k, v / n_dev] for k, v in idle.items()),
                                key=lambda kv: -kv[1])[:top]}}


def reduce_file(path: str, span_names) -> dict | None:
    """Reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``; the
    first of the harness's ``span_names`` marks the window."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device: dict[str, list] = {}
    host: list = []
    names = set(span_names)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            device[plane.name] = [
                (line.name, e.name, e.start_ns, e.duration_ns)
                for line in plane.lines if line.name in DEVICE_LINES
                for e in line.events]
        elif plane.name.startswith("/host:"):
            host += [(e.name, e.start_ns, e.duration_ns)
                     for line in plane.lines for e in line.events
                     if e.name in names]
    return reduce_events(device, host, span_names[0])
