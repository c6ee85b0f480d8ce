"""Pallas TPU kernels: fused K-delta bitmap application (+ analytics).

The DeltaGraph retrieval hot loop applies a root→leaf chain of K deltas to
a membership bitmap.  Done naively that is K passes over the bitmap in HBM
(2·K·W words of traffic).  These kernels stream each bitmap *block* through
VMEM once and apply all K deltas in registers — traffic drops to
(K+2)·BLOCK per block tile, i.e. one read of every delta + one read/write
of the base, the memory-bound optimum.

Layout: packed ``uint32`` words, viewed as ``[.., W/128, 128]`` planes so
every block is a stack of (8, 128) vector tiles.  The grid is
``(batch, word block)``; ``block_w`` words per block (a multiple of 1024 —
one (8, 128) tile — for Mosaic; interpret mode takes any multiple of 128).
The K loop is unrolled inside the kernel body (K is static per path
length).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _planes(x: jnp.ndarray) -> jnp.ndarray:
    """``[.., W]`` words → ``[.., W/128, 128]`` lane planes (W % 128 == 0)."""
    return x.reshape(x.shape[:-1] + (x.shape[-1] // LANES, LANES))


def _pad_words(x: jnp.ndarray, Wp: int) -> jnp.ndarray:
    pad = Wp - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _land(base_ref, adds_ref, dels_ref, K: int) -> jnp.ndarray:
    m = base_ref[...]
    for i in range(K):  # static unroll: K = path length ~ log_k(N)
        m = (m & ~dels_ref[i]) | adds_ref[i]
    return m


def _chain_kernel(base_ref, adds_ref, dels_ref, out_ref, *, K: int):
    out_ref[...] = _land(base_ref, adds_ref, dels_ref, K)


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def delta_apply_chain_batched_pallas(bases: jnp.ndarray, adds: jnp.ndarray,
                                     dels: jnp.ndarray, *, block_w: int,
                                     interpret: bool) -> jnp.ndarray:
    """``bases [B, W]``, ``adds/dels [B, K, W]`` → landed ``[B, W]``.

    The batch is a grid axis, so each step moves one (block_w/128, 128)
    tile stack per plane; W is padded to a multiple of ``block_w``."""
    B, K, W = adds.shape
    if K == 0:
        return bases
    Wp = -(-W // block_w) * block_w
    rb = block_w // LANES
    plane = pl.BlockSpec((None, rb, LANES), lambda b, i: (b, i, 0))
    chain = pl.BlockSpec((None, K, rb, LANES), lambda b, i: (b, 0, i, 0))
    out = pl.pallas_call(
        functools.partial(_chain_kernel, K=K),
        grid=(B, Wp // block_w),
        in_specs=[plane, chain, chain],
        out_specs=plane,
        out_shape=jax.ShapeDtypeStruct((B, Wp // LANES, LANES), jnp.uint32),
        interpret=interpret,
    )(*(_planes(_pad_words(x, Wp)) for x in (bases, adds, dels)))
    return out.reshape(B, Wp)[:, :W]


def delta_apply_chain_pallas(base: jnp.ndarray, adds: jnp.ndarray,
                             dels: jnp.ndarray, *, block_w: int = 1024,
                             interpret: bool) -> jnp.ndarray:
    """Single chain: ``base [W]``, ``adds/dels [K, W]`` → ``[W]``."""
    return delta_apply_chain_batched_pallas(
        base[None], adds[None], dels[None], block_w=block_w,
        interpret=interpret)[0]


# ---------------------------------------------------------------------------
# fused chain + push-style analytics
# ---------------------------------------------------------------------------
#
# The retrieval hot loop ends with analytics over the landed bitmap —
# live-element counts (density), per-node degrees, PageRank push mass.
# Done separately that is a *second* full sweep over the mask (plus an
# unpack pass to feed segment_sum).  The fused kernel emits them while the
# final chain state is still in registers:
#
#   mask   [B, W]       u32 — the landed chain state
#   pop    [B, G]       i32 — per-grid-block popcount partials (Σ = live
#                             count), one SMEM scalar per block
#   accw   [B, W]       f32 — per-word weighted partials: word w's bits
#                             dotted with its 32 slot weights (Σ = PageRank
#                             push mass)
#   live   [B, 32, W]   f32 — the membership indicator as 32 bit-planes
#                             (plane b holds bit b of every word); the ops
#                             wrapper transposes it to slot order, the feed
#                             for the segment_sum per-node degree reduction
#
# Bits are unpacked per bit-plane with lane-local shifts, so no vector
# relayout is needed in the kernel; per-slot weights come in the same
# bit-plane layout.  accw sums the 32 products by halving (b + b⊕16, then
# + b⊕8, ...), the fixed order ref.py uses too, so the fused analytics stay
# bit-identical to the oracle even in float32.


def _halving_sum(xs: list) -> jnp.ndarray:
    while len(xs) > 1:
        h = len(xs) // 2
        xs = [xs[i] + xs[i + h] for i in range(h)]
    return xs[0]


def _fused_kernel(base_ref, adds_ref, dels_ref, *refs, K: int,
                  emit_live: bool, has_weights: bool):
    if has_weights:
        w_ref, *refs = refs
    out_ref, pop_ref, accw_ref, *live_ref = refs
    m = _land(base_ref, adds_ref, dels_ref, K)
    out_ref[...] = m
    mi = jax.lax.bitcast_convert_type(m, jnp.int32)   # Mosaic has no u32→f32
    pc = jax.lax.population_count(mi)
    pop_ref[pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)] = (
        jnp.sum(pc))
    if has_weights or emit_live:
        bits = [((mi >> b) & 1).astype(jnp.float32) for b in range(32)]
    if has_weights:
        accw_ref[...] = _halving_sum([bits[b] * w_ref[b] for b in range(32)])
    else:
        accw_ref[...] = pc.astype(jnp.float32)     # per-word popcount
    if emit_live:
        for b in range(32):
            live_ref[0][b] = bits[b]


@functools.partial(jax.jit,
                   static_argnames=("block_w", "interpret", "emit_live"))
def delta_apply_fused_pallas(bases: jnp.ndarray, adds: jnp.ndarray,
                             dels: jnp.ndarray,
                             weight_planes: jnp.ndarray | None = None, *,
                             block_w: int, interpret: bool,
                             emit_live: bool = True):
    """One pass over each bitmap block: land B K-delta chains *and* emit
    the analytics partials.  ``bases [B, W] u32``, ``adds/dels [B, K, W]
    u32``, ``weight_planes [32, W] f32`` (optional per-slot weights in
    bit-plane layout, shared by the batch).  ``W`` must already be a
    multiple of ``block_w`` (the ops-layer wrapper pads once, so partials
    line up across impls).

    Returns ``(mask [B, W] u32, pop [B, G] i32, accw [B, W] f32,
    live_planes [B, 32, W] f32 | None)``.
    """
    B, K, W = adds.shape
    if W % block_w:
        raise ValueError(f"W={W} is not a multiple of block_w={block_w}")
    if K == 0:   # an all-zero (add, del) row is the identity step
        adds = jnp.zeros((B, 1, W), jnp.uint32)
        dels = jnp.zeros((B, 1, W), jnp.uint32)
        K = 1
    G, R, rb = W // block_w, W // LANES, block_w // LANES
    has_weights = weight_planes is not None
    plane = pl.BlockSpec((None, rb, LANES), lambda b, i: (b, i, 0))
    chain = pl.BlockSpec((None, K, rb, LANES), lambda b, i: (b, 0, i, 0))
    bitplanes = pl.BlockSpec((None, 32, rb, LANES), lambda b, i: (b, 0, i, 0))
    args = [_planes(bases), _planes(adds), _planes(dels)]
    in_specs = [plane, chain, chain]
    if has_weights:
        args.append(_planes(weight_planes))
        in_specs.append(pl.BlockSpec((32, rb, LANES), lambda b, i: (0, i, 0)))
    out_shape = [jax.ShapeDtypeStruct((B, R, LANES), jnp.uint32),
                 jax.ShapeDtypeStruct((B * G,), jnp.int32),
                 jax.ShapeDtypeStruct((B, R, LANES), jnp.float32)]
    out_specs = [plane,
                 pl.BlockSpec(memory_space=pltpu.SMEM),
                 plane]
    if emit_live:
        out_shape.append(
            jax.ShapeDtypeStruct((B, 32, R, LANES), jnp.float32))
        out_specs.append(bitplanes)
    outs = pl.pallas_call(
        functools.partial(_fused_kernel, K=K, emit_live=emit_live,
                          has_weights=has_weights),
        grid=(B, G),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    mask, pop, accw = (outs[0].reshape(B, W), outs[1].reshape(B, G),
                       outs[2].reshape(B, W))
    live = outs[3].reshape(B, 32, W) if emit_live else None
    return mask, pop, accw, live
