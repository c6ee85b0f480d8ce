"""Pure-jnp oracle for the fused K-delta bitmap application.

Semantics (DeltaGraph path application, §4.3): starting from the packed
base membership bitmap, apply K (add, del) bitmap pairs in order::

    m_0 = base
    m_i = (m_{i-1} & ~del_i) | add_i
    out = m_K

All arrays are packed ``uint32`` words; ``adds``/``dels`` are stacked
``[K, W]``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def delta_apply_chain_ref(base: jnp.ndarray, adds: jnp.ndarray,
                          dels: jnp.ndarray) -> jnp.ndarray:
    def step(m, ad):
        a, d = ad
        return (m & ~d) | a, None

    out, _ = jax.lax.scan(step, base, (adds, dels))
    return out


def _halving_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum the trailing axis (a power of two) by repeated halving."""
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]


def delta_apply_fused_ref(base: jnp.ndarray, adds: jnp.ndarray,
                          dels: jnp.ndarray,
                          weights: jnp.ndarray | None = None, *,
                          block_w: int = 1024, emit_live: bool = True):
    """Oracle for the fused chain + analytics kernel.

    Inputs match :func:`delta_apply_chain_ref` plus optional per-slot
    ``weights [W*32] f32``; ``W`` must be a multiple of ``block_w``
    (the ops wrapper pads once for every impl).  Returns

    * ``mask  [W] u32``    — the landed chain state,
    * ``pop   [G] i32``    — per-block popcount partials,
    * ``accw  [W] f32``    — per-word weighted partials (bits of word w
      dotted with its 32 weights, summed by halving: ``x[b] + x[b+16]``,
      then ``+ x[b+8]``, ...; plain per-word popcount when no weights) —
      the fixed grouping and order let the Pallas kernel reproduce these
      bit-for-bit,
    * ``live  [W*32] f32`` — unpacked membership (``None`` unless
      ``emit_live``), the segment_sum degree-reduction feed.
    """
    m = delta_apply_chain_ref(base, adds, dels)
    W = m.shape[0]
    assert W % block_w == 0, "ops wrapper pads W to the block size"
    G = W // block_w
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (W, 32), 1)
    bits = ((m[:, None] >> shifts) & jnp.uint32(1)).astype(jnp.float32)
    pop = (jax.lax.population_count(m).astype(jnp.int32)
           .reshape(G, block_w).sum(axis=1))
    if weights is not None:
        accw = _halving_sum(bits * weights.reshape(W, 32))
    else:
        accw = bits.sum(axis=1)
    live = bits.reshape(-1) if emit_live else None
    return m, pop, accw, live


def delta_apply_chain_prefix_ref(base: jnp.ndarray, adds: jnp.ndarray,
                                 dels: jnp.ndarray) -> jnp.ndarray:
    """Emit every intermediate state of the chain: ``out[i] = m_{i+1}``
    (shape ``[K, W]``).  Used by the multi-interval temporal path, where
    each prefix *is* a query result (one bitmap per interval timepoint) —
    unlike the final-state chain there is no redundant HBM traffic to
    fuse away, every word is an output."""
    def step(m, ad):
        a, d = ad
        m2 = (m & ~d) | a
        return m2, m2

    _, ys = jax.lax.scan(step, base, (adds, dels))
    return ys
