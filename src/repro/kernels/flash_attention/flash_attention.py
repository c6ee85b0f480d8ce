"""Pallas TPU flash attention (FlashAttention-2-style online softmax).

Grid ``(B*H, num_q_blocks, num_kv_blocks)`` with the kv dimension innermost
and sequential; running max / denominator / accumulator live in VMEM
scratch, so KV streams HBM→VMEM block by block and the score matrix never
materializes.  Q/KV block sizes are multiples of the 128-lane MXU tiling.

Supports causal masking, sliding windows (Gemma-style local layers), and a
``q_offset`` for decode (Sq « Sk against a long KV cache).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int | None,
            q_offset: int, kv_len: int, bq: int, bk: int, nk: int):
    kv_i = pl.program_id(2)
    q_i = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, :, :].astype(jnp.float32)          # [bq, d]
    k = k_ref[0, :, :].astype(jnp.float32)          # [bk, d]
    v = v_ref[0, :, :].astype(jnp.float32)          # [bk, d]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = q_i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    kpos = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < kv_len                             # drop padded keys
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                              # [bq]
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    p = jnp.where(mask, p, 0.0)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_cur

    @pl.when(kv_i == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, :, :] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "q_offset", "scale", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, window: int | None = None,
                           q_offset: int = 0, scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool) -> jnp.ndarray:
    """q: [B, H, Sq, D]; k, v: [B, H, Sk, D] (GQA pre-broadcast by ops.py)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    Dv = v.shape[-1]  # may differ from D (MLA)
    scale = scale if scale is not None else D ** -0.5
    bq = min(block_q, max(Sq, 8))
    bk = min(block_k, max(Sk, 8))
    Sqp = -(-Sq // bq) * bq
    Skp = -(-Sk // bk) * bk
    if Sqp != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    if Skp != Sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Skp - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Skp - Sk), (0, 0)))
    nq, nk = Sqp // bq, Skp // bk
    qf = q.reshape(B * H, Sqp, D)
    kf = k.reshape(B * H, Skp, D)
    vf = v.reshape(B * H, Skp, Dv)

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             window=window, q_offset=q_offset, kv_len=Sk,
                             bq=bq, bk=bk, nk=nk)
    out = pl.pallas_call(
        kern,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sqp, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sqp, Dv)[:, :, :Sq, :]
