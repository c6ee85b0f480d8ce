"""Double-buffered host→device staging for chunked chain application.

The device retrieval path lands a ``[B, K, W]`` stack of delta bit-planes.
Built monolithically, the timeline serializes: decode/pack all K planes on
the host, one big ``device_put``, then the kernel.  :class:`DeviceStager`
chunks the K axis and pipelines the stages instead — while the kernel
applies chunk *i*, the host builds (codec-decode → ``np_from_indices``
pack) and ``device_put``s chunk *i+1*.  JAX dispatch is asynchronous, so
``apply`` returns as soon as the work is enqueued and the host immediately
moves on to staging the next chunk; with ``depth=2`` (double buffering)
exactly one chunk is ever in flight ahead of the compute stream, bounding
resident staging memory to two chunks.

Chunked application is exact: the delta chain is a left fold of bitwise
steps, so landing it ``chunk_k`` rows at a time produces bit-identical
masks (pinned by ``tests/test_device_pipeline.py``).

Every host↔device copy of the retrieval path goes through
:func:`to_device` and :func:`to_host`, which run each copy under an
``h2d.put`` / ``d2h.copy`` span carrying the ``bytes`` it moves.
"""
from __future__ import annotations

import os
from collections import deque
from typing import Any, Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .spans import span


def to_device(a) -> jax.Array:
    """``jnp.asarray(a)`` under an ``h2d.put`` span carrying the bytes the
    device array holds; a device array is returned as it is."""
    if isinstance(a, jax.Array):
        return a
    a = np.asarray(a)
    # a span's stats are fixed when it opens: the bytes the device array
    # will hold, in the dtype JAX gives it
    n = a.size * jax.dtypes.canonicalize_dtype(a.dtype).itemsize
    with span("h2d.put", bytes=n):
        return jnp.asarray(a)


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``; a device array is read back under a ``d2h.copy``
    span carrying its bytes (the read waits for the program producing
    it).  Every call on a device array counts as a copy, so the path
    reads each array back once and hands the host copy on."""
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    with span("d2h.copy", bytes=x.nbytes):
        return np.asarray(x)


def stream_chunk_k(default: int = 8) -> int:
    """Chunk length along K for the streamed path (``REPRO_STREAM_CHUNK``
    env override; values < 1 disable streaming — monolithic apply)."""
    try:
        return int(os.environ.get("REPRO_STREAM_CHUNK", default))
    except ValueError:
        return default


class DeviceStager:
    """Pipelines ``build → put → apply`` over a chunk sequence.

    ``put_fn`` (default :func:`to_device`, which spans each copy) is
    injectable, so tests can record the order of builds, puts and applies
    in their own closures.
    """

    def __init__(self, depth: int = 2,
                 put_fn: Callable[[Any], Any] | None = None,
                 prefetcher=None) -> None:
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self.depth = int(depth)
        self.put_fn = put_fn if put_fn is not None else to_device
        self.prefetcher = prefetcher

    def stream(self, num_chunks: int, build_chunk: Callable[[int], Sequence],
               apply_chunk: Callable[[Any, tuple], Any], carry: Any) -> Any:
        """Fold ``apply_chunk`` over ``num_chunks`` staged chunks.

        ``build_chunk(i)`` produces the host arrays for chunk *i* (run on a
        prefetch worker when one is attached, overlapping the numpy pack
        with device compute); ``apply_chunk(carry, device_arrays)`` advances
        the chain.  Up to ``depth`` chunks are staged ahead of the apply
        cursor.
        """
        if num_chunks <= 0:
            return carry

        # one build kept in flight on a prefetch worker: consuming chunk
        # i's host arrays immediately kicks off chunk i+1's build, so the
        # numpy pack overlaps the put + kernel dispatch for chunk i
        ahead: tuple[int, Any] | None = None

        def kick(i: int) -> None:
            nonlocal ahead
            ahead = ((i, self.prefetcher.submit_fn(build_chunk, i))
                     if self.prefetcher is not None and i < num_chunks
                     else None)

        def obtain(i: int):
            nonlocal ahead
            if ahead is not None and ahead[0] == i:
                host = ahead[1].result()
            else:
                host = build_chunk(i)
            kick(i + 1)
            return host

        kick(0)
        staged: deque[tuple] = deque()
        next_i = 0
        while staged or next_i < num_chunks:
            while next_i < num_chunks and len(staged) < self.depth:
                staged.append(tuple(self.put_fn(h) for h in obtain(next_i)))
                next_i += 1
            carry = apply_chunk(carry, staged.popleft())
        return carry
