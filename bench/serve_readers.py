"""Arithmetic the served cells' metric readers share, beside
``bench/readers.py``."""
from __future__ import annotations


def program_seconds(run, part: str) -> float | None:
    """Device seconds in the traced window of the programs whose name
    holds ``part``, from the trace reduction's per-program times
    (``breakdown.device_ops``, the ten programs that took most time);
    ``None`` untraced or where no such program ran."""
    t = run.trace
    if t is None:
        return None
    return sum(s for name, s in t["breakdown"]["device_ops"]
               if part in name) or None
