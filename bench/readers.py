"""Arithmetic shared by the metric readers in ``bench/metrics/``.

A reader gets the run (``bench/run.py``'s ``Run``): the ``window`` the
driver returned, ``seconds``, ``setup_s``, the history's own ``sizes``
(``nodes``, ``edges``, before the store's capacity), the program's
``counters`` before and after the window (``run.delta(name)``), the
reduced ``trace`` of a traced run, and the ``device_kind``.
"""
from __future__ import annotations

from .peaks import peaks


def ratio(num: float | None, den: float | None,
          scale: float = 1.0) -> float | None:
    if num is None or not den:
        return None
    return scale * num / den


def idle_share(run) -> float | None:
    t = run.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def roofline(run, prefix: str, call_bytes) -> float | None:
    """Share of the HBM roofline of the kernels whose name starts with
    ``prefix``: the bytes their calls need (``call_bytes(text, nodes,
    edges)``, from ``bench/costs.py``) over the time of the programs that
    ran them, over the device's peak bytes per second."""
    t = run.trace
    if t is None:
        return None
    ks = [k for n, k in t["kernels"].items() if n.startswith(prefix)]
    secs = sum(k["seconds"] for k in ks)
    if not secs:
        return None
    need = sum(n * call_bytes(text, **run.sizes)
               for k in ks for text, n in k["shapes"].items())
    if not need:
        return None
    return 100.0 * need / secs / peaks(run.device_kind)["hbm_bytes_per_s"]
