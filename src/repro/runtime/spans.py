"""Named spans of the retrieval path, on the profiler's clock.

``span(name)`` is a :class:`jax.profiler.TraceAnnotation`: while a
profiler trace is active it lands in the trace's host plane, on the line
of the thread that opened it and on the clock the device planes share;
otherwise it costs one object and a check.  Keyword arguments ride on the
event as stats (the transfer helpers in :mod:`repro.runtime.staging` put
each copy's ``bytes`` there).

Names are ``layer.what``, stable, and all listed in :data:`NAMES`, which
trace readers use to pick the program's spans out of a trace:

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
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

NAMES = ("retrieve.plan", "slice.quads", "codec.decode", "kv.wait",
         "host.pack", "host.unpack", "h2d.put", "d2h.copy",
         "kernel.dispatch", "kernel.bucket", "loader.batch",
         "loader.assemble")


def span(name: str, **stats) -> TraceAnnotation:
    """A trace span named ``name`` (one of :data:`NAMES`)."""
    return TraceAnnotation(name, **stats)
