"""Public jit'd wrappers for fused delta-chain application + analytics.

``impl``/``interpret`` default to the process-wide policy
(:mod:`repro.kernels.policy`): ``REPRO_KERNEL=pallas|xla`` or backend
detection (Pallas compiled on TPU, XLA elsewhere; interpret mode only
ever auto-selected off-TPU).  Callers no longer thread kernel flags —
explicit arguments remain as overrides for tests and benchmarks.
"""
from __future__ import annotations

import functools
from collections import Counter
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import policy
from ...runtime.spans import span
from ...runtime.staging import to_host
from .delta_apply import (delta_apply_chain_batched_pallas,
                          delta_apply_chain_pallas, delta_apply_fused_pallas)
from .ref import (delta_apply_chain_prefix_ref, delta_apply_chain_ref,
                  delta_apply_fused_ref)

# Shape bucketing for the jit'd XLA paths: chain calls arrive with
# arbitrary (B, K, W) — every distinct shape would otherwise compile its
# own executable, and a retrieval service sees a new shape per plan.
# Padding B and K up to powers of two (all-zero (add, del) rows are
# identity steps; extra batch rows are dropped) and W up to a 128-word
# lane multiple collapses the shape space to a handful of buckets that
# stay hot in the compile cache.
_W_ALIGN = 128

# Retraces per entry point (a trace == a compile for these jits): the
# bucketing above bounds it to O(log) distinct shapes per entry — pinned
# by tests/test_kernels.py::test_recompile_counts_bounded.
trace_counts: Counter = Counter()


def reset_trace_counts() -> None:
    trace_counts.clear()


def _bucket(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _pad_axis(a: jnp.ndarray, axis: int, target: int) -> jnp.ndarray:
    pad = target - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _counted(name: str, fn):
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        trace_counts[name] += 1          # runs at trace time only
        return fn(*args, **kwargs)
    return wrapper


_chain_jit = jax.jit(_counted("chain", delta_apply_chain_ref))
_chain_batched_jit = jax.jit(
    _counted("chain_batched", jax.vmap(delta_apply_chain_ref)))
_chain_prefix_batched_jit = jax.jit(
    _counted("chain_prefix_batched", jax.vmap(delta_apply_chain_prefix_ref)))


def delta_apply_chain(base: jnp.ndarray, adds: jnp.ndarray, dels: jnp.ndarray,
                      *, impl: str | None = None, block_w: int = 1024,
                      interpret: bool | None = None) -> jnp.ndarray:
    with span("kernel.dispatch"):
        impl, interpret = policy.resolve(impl, interpret)
        W = base.shape[0]
        Kp = _bucket(adds.shape[0])
        adds, dels = _pad_axis(adds, 0, Kp), _pad_axis(dels, 0, Kp)
        if impl == "xla":
            Wp = -(-W // _W_ALIGN) * _W_ALIGN
            out = _chain_jit(_pad_axis(base, 0, Wp), _pad_axis(adds, 1, Wp),
                             _pad_axis(dels, 1, Wp))
            return out[:W]
        return delta_apply_chain_pallas(base, adds, dels, block_w=block_w,
                                        interpret=interpret)


def delta_apply_chain_batched(bases: jnp.ndarray, adds: jnp.ndarray,
                              dels: jnp.ndarray, *, impl: str | None = None,
                              block_w: int = 1024,
                              interpret: bool | None = None) -> jnp.ndarray:
    """Multi-snapshot apply: ``B`` sibling chains in one call.

    ``bases [B, W]``, ``adds/dels [B, K, W]`` (chains zero-padded to a
    common ``K``; an all-zero ``(adds, dels)`` row is the identity step).
    Sibling branches after a plan Fork execute as one batched pass — one
    kernel launch and one sweep over the stacked bit-planes instead of
    ``B`` sequential chain calls (the Pallas kernel carries ``B`` as a
    grid axis).
    """
    with span("kernel.dispatch"):
        impl, interpret = policy.resolve(impl, interpret)
        B, K, W = adds.shape
        Bp, Kp = _bucket(B), _bucket(K)
        bases = _pad_axis(bases, 0, Bp)
        adds, dels = (_pad_axis(_pad_axis(x, 1, Kp), 0, Bp)
                      for x in (adds, dels))
        if impl == "xla":
            Wp = -(-W // _W_ALIGN) * _W_ALIGN
            out = _chain_batched_jit(_pad_axis(bases, 1, Wp),
                                     _pad_axis(adds, 2, Wp),
                                     _pad_axis(dels, 2, Wp))
        else:
            out = delta_apply_chain_batched_pallas(bases, adds, dels,
                                                   block_w=block_w,
                                                   interpret=interpret)
        return out[:B, :W]


def delta_apply_chain_prefix(base: jnp.ndarray, adds: jnp.ndarray,
                             dels: jnp.ndarray) -> jnp.ndarray:
    """All K intermediate chain states ``[K, W]`` (``out[i]`` = state after
    delta ``i``).  The temporal engine's multi-interval path vmaps this
    over stacked intervals — every prefix is a returned snapshot bitmap,
    so (unlike :func:`delta_apply_chain`) there is no fused-kernel variant:
    each word is genuinely written once per step either way."""
    with span("kernel.dispatch"):
        return delta_apply_chain_prefix_ref(base, adds, dels)


def delta_apply_chain_prefix_batched(bases: jnp.ndarray, adds: jnp.ndarray,
                                     dels: jnp.ndarray) -> jnp.ndarray:
    """Vmapped prefix chains: ``bases [B, W]``, ``adds/dels [B, K, W]`` →
    ``[B, K, W]`` per-timepoint bitmaps for B intervals in one pass.
    Shape-bucketed and jit'd like :func:`delta_apply_chain_batched`; the
    padded identity rows repeat the final state and are sliced away."""
    with span("kernel.dispatch"):
        B, K, W = adds.shape
        Wp = -(-W // _W_ALIGN) * _W_ALIGN
        Bp, Kp = _bucket(B), _bucket(K)
        out = _chain_prefix_batched_jit(
            _pad_axis(_pad_axis(bases, 1, Wp), 0, Bp),
            _pad_axis(_pad_axis(_pad_axis(adds, 2, Wp), 1, Kp), 0, Bp),
            _pad_axis(_pad_axis(_pad_axis(dels, 2, Wp), 1, Kp), 0, Bp))
        return out[:B, :K, :W]


# ---------------------------------------------------------------------------
# fused chain + analytics
# ---------------------------------------------------------------------------


class FusedOut(NamedTuple):
    """Result of one fused delta-apply + analytics pass.

    ``mask [.., W] u32`` is the landed chain state; ``pop [.., G] i32``
    per-block popcount partials; ``accw [.., W] f32`` per-word weighted
    partials; ``live [.., W*32] f32`` the unpacked membership indicator
    (``None`` unless requested — it is the segment_sum degree feed).
    Partials are identical across impls (fixed per-word/per-block
    reduction groups), so the totals below are too.
    """
    mask: jnp.ndarray
    pop: jnp.ndarray
    accw: jnp.ndarray
    live: jnp.ndarray | None

    def live_count(self):
        """Total live elements (int; summed over the trailing axis)."""
        return to_host(self.pop).sum(axis=-1)

    def weighted_total(self):
        """Σ weights over live slots, f32 (PageRank push mass)."""
        return np.asarray(self.accw, np.float32).sum(axis=-1,
                                                     dtype=np.float32)


_fused_xla_jit = jax.jit(_counted("fused", delta_apply_fused_ref),
                         static_argnames=("block_w", "emit_live"))


def _fused_batched_ref(bases, adds, dels, weights, *, block_w, emit_live):
    return jax.vmap(
        lambda b, a, d: delta_apply_fused_ref(
            b, a, d, weights, block_w=block_w, emit_live=emit_live)
    )(bases, adds, dels)


_fused_batched_xla_jit = jax.jit(
    _counted("fused_batched", _fused_batched_ref),
    static_argnames=("block_w", "emit_live"))


@functools.partial(jax.jit,
                   static_argnames=("block_w", "interpret", "emit_live"))
def _fused_batched_pallas(bases, adds, dels, weights, *, block_w, interpret,
                          emit_live):
    """The Pallas kernel works on bit-planes (plane ``b`` = bit ``b`` of
    every word): slot-order weights go in as ``[32, W]`` planes and the
    live indicator comes back to slot order ``[B, W*32]``."""
    Wp = bases.shape[-1]
    planes = None if weights is None else weights.reshape(Wp, 32).T
    mask, pop, accw, live = delta_apply_fused_pallas(
        bases, adds, dels, planes, block_w=block_w, interpret=interpret,
        emit_live=emit_live)
    if live is not None:
        live = live.transpose(0, 2, 1).reshape(live.shape[0], Wp * 32)
    return mask, pop, accw, live


def _fused_pad(base, adds, dels, weights, block_w):
    """Pad W to a block multiple and K to its bucket — once, identically,
    for every impl, so partials line up bit-for-bit across impls."""
    K, W = adds.shape[-2:]
    Wp = -(-W // block_w) * block_w
    Kp = _bucket(K)
    base = _pad_axis(base, base.ndim - 1, Wp)
    adds = _pad_axis(_pad_axis(adds, adds.ndim - 1, Wp), adds.ndim - 2, Kp)
    dels = _pad_axis(_pad_axis(dels, dels.ndim - 1, Wp), dels.ndim - 2, Kp)
    if weights is not None:
        weights = _pad_axis(jnp.asarray(weights, jnp.float32), 0, Wp * 32)
    return base, adds, dels, weights, W


def delta_apply_fused(base: jnp.ndarray, adds: jnp.ndarray,
                      dels: jnp.ndarray,
                      weights: jnp.ndarray | None = None, *,
                      impl: str | None = None, block_w: int = 1024,
                      interpret: bool | None = None,
                      emit_live: bool = True) -> FusedOut:
    """Fused retrieval + analytics: land the K-delta chain over ``base``
    and, in the same pass over each bitmap block, emit per-block popcount
    partials, per-word weighted partials (``weights [W*32] f32``, e.g.
    per-slot PageRank contributions) and the unpacked live indicator that
    feeds the segment_sum kernel's per-node degree reduction.

    ``pop``/``accw`` come back over the padded width (zero padding
    contributes nothing); ``mask`` and ``live`` are trimmed to ``W``.
    """
    with span("kernel.dispatch"):
        impl, interpret = policy.resolve(impl, interpret)
        base, adds, dels, weights, W = _fused_pad(base, adds, dels, weights,
                                                  block_w)
        if impl == "xla":
            mask, pop, accw, live = _fused_xla_jit(
                base, adds, dels, weights, block_w=block_w,
                emit_live=emit_live)
        else:
            outs = _fused_batched_pallas(
                base[None], adds[None], dels[None], weights, block_w=block_w,
                interpret=interpret, emit_live=emit_live)
            mask, pop, accw, live = (None if x is None else x[0] for x in outs)
        return FusedOut(mask[:W], pop, accw[:W],
                        live[:W * 32] if live is not None else None)


def delta_apply_fused_batched(bases: jnp.ndarray, adds: jnp.ndarray,
                              dels: jnp.ndarray,
                              weights: jnp.ndarray | None = None, *,
                              impl: str | None = None, block_w: int = 1024,
                              interpret: bool | None = None,
                              emit_live: bool = True) -> FusedOut:
    """Batched fused apply+analytics: ``bases [B, W]``, ``adds/dels
    [B, K, W]``, one shared ``weights [W*32]`` — B chains land and emit
    their analytics partials in a single pass (B is bucketed; padded rows
    are dropped from every output)."""
    with span("kernel.dispatch"):
        impl, interpret = policy.resolve(impl, interpret)
        B = bases.shape[0]
        bases, adds, dels, weights, W = _fused_pad(bases, adds, dels, weights,
                                                   block_w)
        Bp = _bucket(B)
        bases = _pad_axis(bases, 0, Bp)
        adds = _pad_axis(adds, 0, Bp)
        dels = _pad_axis(dels, 0, Bp)
        if impl == "xla":
            mask, pop, accw, live = _fused_batched_xla_jit(
                bases, adds, dels, weights, block_w=block_w,
                emit_live=emit_live)
        else:
            mask, pop, accw, live = _fused_batched_pallas(
                bases, adds, dels, weights, block_w=block_w,
                interpret=interpret, emit_live=emit_live)
        return FusedOut(mask[:B, :W], pop[:B], accw[:B, :W],
                        live[:B, :W * 32] if live is not None else None)
