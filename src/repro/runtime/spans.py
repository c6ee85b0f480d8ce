"""Named spans of the retrieval path, on the profiler's clock.

``span(name)`` is a :class:`jax.profiler.TraceAnnotation`: while a
profiler trace is active it lands in the trace's host plane, on the line
of the thread that opened it and on the clock the device planes share;
otherwise it costs one object and a check.  Keyword arguments ride on the
event as stats (the transfer helpers in :mod:`repro.runtime.staging` put
each copy's ``bytes`` there).

Names are ``layer.what``, stable, and all listed in :data:`NAMES`, which
trace readers use to pick the program's spans out of a trace.  The
retrieval path:

* ``retrieve.plan``: singlepoint / Steiner planning on the skeleton;
* ``slice.quads``: net event slices between interval points;
* ``codec.decode``: payload decompression and deserialization, on
  whichever thread runs it;
* ``kv.wait``: the caller's thread blocked on a fetch (a prefetch
  future, or a synchronous fetch);
* ``host.pack`` / ``host.unpack``: index lists to packed bit planes, and
  packed planes back to bool masks;
* ``h2d.put`` / ``d2h.copy``: host-device copies (``d2h.copy`` blocks on
  the program that produces the array, so it includes that device time);
* ``kernel.dispatch``: host time of a kernel wrapper (padding, host-side
  preprocessing, dispatch);
* ``kernel.bucket``: building and uploading a universe's ``segment_sum``
  bucket tables (``runtime.jax_exec.edge_buckets``), once per universe
  shape, outside ``kernel.dispatch``;
* ``loader.batch``: one batch of ``SnapshotBatchLoader``, closed before
  the batch is yielded;
* ``loader.assemble``: a batch's feature, mask and label arrays.

The served path, each opened through :class:`timed`, once a document or
a point (never per edge or per iteration), so that a document's
envelope reports the same times the trace shows:

* ``sched.dispatch``: the scheduler's execution of one dispatched group
  of documents on a worker (``docs``: its size);
* ``evolve.advance``: ``IntervalSlicer.advance`` from one evolve point
  to the next, on the host;
* ``solve.fixpoint``: one fixpoint solve (``op``: ``pagerank`` or
  ``components``; ``iters`` and ``edges``, the padded live-edge length
  Ec, set when it ends; a PageRank solve adds ``impl``, ``bucketed`` or
  ``dense``, and ``rows``, the entries an iteration reads: on the
  bucketed path the R·C lanes it gathers, padding included, on the
  dense one the N² matrix): compaction, upload, the device program,
  read-back;
* ``reply.render``: rendering and encoding a result envelope.
"""
from __future__ import annotations

import contextlib
import contextvars
import time

from jax.profiler import TraceAnnotation

NAMES = ("retrieve.plan", "slice.quads", "codec.decode", "kv.wait",
         "host.pack", "host.unpack", "h2d.put", "d2h.copy",
         "kernel.dispatch", "kernel.bucket", "loader.batch",
         "loader.assemble", "sched.dispatch", "evolve.advance",
         "solve.fixpoint", "reply.render")

# the seconds of each timed span, by name, of the collect() open here
_SINK: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "spans_sink", default=None)


def span(name: str, **stats) -> TraceAnnotation:
    """A trace span named ``name`` (one of :data:`NAMES`)."""
    return TraceAnnotation(name, **stats)


class timed:
    """A span that times itself: entering opens ``span(name, **stats)``;
    leaving adds its wall seconds to the :func:`collect` open in this
    context, if any, under ``name``.  ``elapsed()`` reads the seconds so
    far; ``set(**stats)`` adds stats known only at the end."""

    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str, **stats) -> None:
        self.name = name
        self._ann = TraceAnnotation(name, **stats)

    def __enter__(self) -> "timed":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def elapsed(self) -> float:
        return time.perf_counter() - self._t0

    def set(self, **stats) -> None:
        self._ann.set_metadata(**stats)

    def __exit__(self, *exc) -> None:
        sink = _SINK.get()
        if sink is not None:
            sink[self.name] = sink.get(self.name, 0.0) + self.elapsed()
        self._ann.__exit__(*exc)


@contextlib.contextmanager
def collect():
    """Collects the seconds of the :class:`timed` spans that close in this
    context (this thread) while it is open: yields ``{name: seconds}``."""
    sink: dict[str, float] = {}
    token = _SINK.set(sink)
    try:
        yield sink
    finally:
        _SINK.reset(token)
