"""Blockwise (flash) attention as a Pallas TPU kernel.

TPU adaptation of the FlashAttention blocking scheme (DESIGN.md §6): the
(q-block × kv-block) score tile lives in VMEM, sized so that q/k/v tiles and
the f32 accumulator fit comfortably; matmul dims are multiples of the
128-wide MXU.  The kv-block index is the *innermost* grid dimension, so the
online-softmax carry (m, l, acc) persists in VMEM scratch across kv steps of
one q block (the canonical Mosaic revisiting pattern).

GQA is handled in the index maps: query head ``h`` reads kv head ``h // g``
— no kv replication in HBM.

Causal masking skips fully-masked tiles via ``pl.when`` (the tile still
occupies a grid step, but no FLOPs are issued — on TPU, Mosaic elides the
work; the roofline model counts only the issued tiles).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 causal: bool, scale: float, block_q: int, block_k: int,
                 n_kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        # tile is live unless it is entirely above the diagonal
        run = (ki * block_k) <= (qi * block_q + block_q - 1)

    @pl.when(run if causal else True)
    def _compute():
        q = q_ref[0, :, :].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, :, :].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # fully-masked rows keep m == NEG_INF; exp through a zeroed-out
        # surrogate so they contribute nothing (robust to block_q != block_k)
        safe_m = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - safe_m), 0.0)
        corr = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - safe_m), 0.0)
        l_scr[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, :, :] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_bhsd(q, k, v, causal: bool = True, block_q: int = 128,
                         block_k: int = 128, interpret: bool | None = None):
    """q: (BH_q, S, D); k/v: (BH_kv, T, D) with BH_q = BH_kv * g.

    Head-major layout — ``ops.flash_attention`` handles the (B, S, H, D)
    transposes and GQA head mapping.
    """
    BHq, S, D = q.shape
    BHkv, T, _ = k.shape
    g = BHq // BHkv
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    nq, nk = S // block_q, T // block_k
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _attn_kernel, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, n_kv_blocks=nk)

    grid = (BHkv, g, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D),
                         lambda bh, gi, qi, ki: (bh * g + gi, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, gi, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, gi, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, gi, qi, ki: (bh * g + gi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BHq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
