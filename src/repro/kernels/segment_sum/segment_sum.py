"""Pallas TPU kernel: bucketed segment-sum (GNN message aggregation).

TPUs have no efficient scatter; the MXU does 128×128 matmuls.  The
adaptation (taxonomy §B.11 "one-hot matmul"): host-side, edges sorted by
destination are bucketed so that each grid step owns one *node block* of
``block_n`` consecutive destinations together with its (padded) edge
block; in-kernel the scatter becomes ``data · onehot(local_dst)ᵀ`` — one
dense matmul per tile, no data-dependent control flow.

Edges lie on the lane axis (``data [NB, D, ME]``, ``out [NB, D, bn]``): a
narrow feature width — ``D = 1`` for degree counts — then pads only the
sublane axis of a tile, not 128 lanes per edge.

Bucketing is a one-off host preprocessing of the (static) graph structure;
messages then flow through with zero scatter at train-step time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(data_ref, lid_ref, out_ref, *, block_n: int):
    data = data_ref[...]                          # [D, me]
    lid = lid_ref[...]                            # [1, me] local dst
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (block_n, lid.shape[1]), 0)
              == lid).astype(data.dtype)          # [bn, me]
    out_ref[...] = jax.lax.dot_general(           # [D, bn]
        data, onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def segment_sum_bucketed(data: jnp.ndarray, local_ids: jnp.ndarray, *,
                         block_n: int, interpret: bool) -> jnp.ndarray:
    """data: [NB, D, ME] padded per-bucket edge features; local_ids:
    [NB, ME] destination offsets within the bucket (−1 = padding, routed to
    a dead row).  Returns [NB, D, block_n] per-bucket sums.

    Each grid step's blocks span whole trailing dims, so any ME and D
    satisfy the (8, 128) tiling rule."""
    NB, D, ME = data.shape
    lid = jnp.where(local_ids >= 0, local_ids, block_n)  # pad → off-block
    return pl.pallas_call(
        functools.partial(_kernel, block_n=block_n),
        grid=(NB,),
        in_specs=[
            pl.BlockSpec((None, D, ME), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, ME), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, D, block_n), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((NB, D, block_n), data.dtype),
        interpret=interpret,
    )(data, lid.reshape(NB, 1, ME))
