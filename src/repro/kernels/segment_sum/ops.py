"""Public segment-sum wrapper + host-side edge bucketing."""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from .. import policy
from ...runtime.spans import span
from ...runtime.staging import to_device, to_host
from .ref import segment_sum_ref
from .segment_sum import segment_sum_bucketed


def bucket_edges(seg_ids: np.ndarray, num_segments: int, block_n: int
                 ) -> tuple[np.ndarray, np.ndarray, int]:
    """Host preprocessing: sort edges by segment, bucket into node blocks of
    ``block_n`` destinations, pad each bucket's edge list to the max.

    Returns (order, local_ids, max_edges): gather ``data[order]`` then
    reshape to [NB, ME, D]; ``local_ids`` is [NB, ME] with -1 padding.
    ME is the largest bucket rounded up to whole 128-lane rows.
    """
    seg_ids = np.asarray(seg_ids)
    order = np.argsort(seg_ids, kind="stable")
    sorted_ids = seg_ids[order]
    NB = -(-num_segments // block_n)
    bucket_of = sorted_ids // block_n
    counts = np.bincount(bucket_of, minlength=NB)
    ME = -(-max(int(counts.max(initial=0)), 1) // 128) * 128
    out_order = np.zeros((NB, ME), np.int64)
    local = np.full((NB, ME), -1, np.int32)
    starts = np.zeros(NB + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    for b in range(NB):
        c = counts[b]
        sl = slice(starts[b], starts[b] + c)
        out_order[b, :c] = order[sl]
        local[b, :c] = sorted_ids[sl] - b * block_n
    return out_order, local, ME


def segment_sum(data: jnp.ndarray, seg_ids, num_segments: int, *,
                impl: str | None = None, block_n: int = 128,
                buckets: tuple | None = None,
                interpret: bool | None = None) -> jnp.ndarray:
    """Segment sum with selectable implementation.

    impl='xla'    → jax.ops.segment_sum (scatter; lowering/roofline path)
    impl='pallas' → bucketed one-hot-matmul kernel; ``buckets`` may carry
                    precomputed ``bucket_edges`` output (static graphs),
                    its tables on the host or already on the device
                    (``runtime.jax_exec.edge_buckets`` keeps a universe's
                    there); ``seg_ids`` is then not read.
    impl=None     → resolved by :mod:`repro.kernels.policy` (REPRO_KERNEL
                    env, else backend detection).

    ``seg_ids`` may live on the host or the device; the Pallas path
    buckets them on the host, so a host array spares it a read back.
    """
    impl, interpret = policy.resolve(impl, interpret)
    with span("kernel.dispatch"):
        if impl == "xla":
            return segment_sum_ref(data, to_device(seg_ids), num_segments)
        if impl == "pallas":
            if data.shape[0] == 0:      # no edge: nothing to gather
                return jnp.zeros((num_segments, data.shape[-1]), data.dtype)
            if buckets is None:
                buckets = bucket_edges(to_host(seg_ids), num_segments,
                                       block_n)
            out_order, local, ME = buckets
            NB, D = local.shape[0], data.shape[-1]
            gathered = jnp.take(data.T, to_device(out_order.reshape(-1)),
                                axis=1).reshape(D, NB, ME).transpose(1, 0, 2)
            out = segment_sum_bucketed(gathered, to_device(local),
                                       block_n=block_n, interpret=interpret)
            return out.transpose(0, 2, 1).reshape(NB * block_n,
                                                  D)[:num_segments]
    raise ValueError(f"unknown impl {impl!r}")
