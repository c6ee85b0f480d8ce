"""The general load generator: a traffic mix is a data file under
``bench/traffic/`` that names its ``path``, the entry point users call,
and the parameters of its load.  The path is ``bench/paths/<path>.py``
(found by ``bench/parts.py``), which reads those parameters.

A path module has ``SPANS``, the harness spans it opens (the window's
first, which ``bench/trace.py`` takes as the window), and
``Driver(cell, traffic)``, which can ``build`` the load from the seed,
``warm`` exactly the shapes it will use, ``drive`` the window under a
tracer, ``close``, and ``verify`` the answers the timed path produced
against the plain reference (``bench/reference.py``) once the window has
closed.  This module keeps what the paths share.
"""
from __future__ import annotations

import numpy as np


def interval(rng, tmax: int, span: float, points: int,
             tail: int = 0) -> list[int]:
    """``points`` evenly spaced times over ``span`` of the history, ending
    at least ``tail`` before its end."""
    width = max(int(tmax * span), points)
    start = int(rng.integers(0, max(tmax - tail - width, 1)))
    return [int(t) for t in np.linspace(start, start + width, points)]
