"""Single-token (decode) attention straight from a paged KV pool.

Every slot attends its one new query token over its own cache, which
lives in fixed-size blocks of a shared pool (``serve/paged.py``): a block
table maps the slot's logical block ``j`` to a pool block id.  The kernel
reads each slot's blocks below ``ceil(length / block_size)`` and nothing
else — no per-slot full-width copy of the cache is ever built.

Layout.  The pools are stacked over the model's layer blocks,
``(n_layers, n_pool_blocks, Hkv, block_size, D)``: one pool block is one
contiguous ``(Hkv, block_size, D)`` run, so a block costs ONE DMA per
pool whatever the KV-head count, and each head's tile is
``(block_size, D)``.  ``layer`` (scalar prefetch) picks the layer inside
the stacked pool, so the caller never slices a layer out.

Schedule.  One program walks every (slot, chunk) pair in turn; a chunk
is ``pages`` consecutive logical blocks (``chunk_pages``: about
``CHUNK_TOKENS`` tokens).  The next pair's blocks are DMA'd into the
other half of a double buffer while the current pair computes, across
slot boundaries too.  Each chunk runs an online-softmax update in
float32 for all ``g = Hq / Hkv`` query heads of each KV head at once;
both products take the pool's dtype on the MXU and accumulate in
float32 (the probabilities are rounded to it, as in the dense path).
Blocks past a slot's length inside its last chunk are never copied; their
rows (stale or uninitialised VMEM) are masked out of both the scores and
the values, so they cannot leak NaNs.  Every slot has ``length >= 1``
(an inactive slot reads one row of the null block 0), so every chunk
processed holds a real row and every output is finite.
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

#: tokens per chunk the kernel computes at once: the chunk's blocks are
#: one DMA batch, and a slot computes less than a chunk of masked rows
#: past its length.  On a TPU v5e, 1,024- and 2,048-token chunks read
#: slots at full width faster but slots with a few live blocks slower.
CHUNK_TOKENS = 512


def chunk_pages(block_size: int, max_blocks: int) -> int:
    """Blocks per chunk: ``CHUNK_TOKENS`` worth, at least one, at most a
    slot's whole table."""
    return max(1, min(max_blocks, CHUNK_TOKENS // block_size))


def _paged_kernel(layer_ref, lengths_ref, tables_ref,      # SMEM prefetch
                  q_ref, k_hbm, v_hbm,                       # inputs
                  o_ref,                                     # output
                  kbuf, vbuf, sems,                          # scratch
                  *, pages: int, max_blocks: int, scale: float):
    n_slots, n_kv, g, _ = q_ref.shape
    bs = k_hbm.shape[3]
    width = pages * bs
    layer = layer_ref[0]

    def n_live(s):
        return (lengths_ref[s] + bs - 1) // bs

    def dma(s, c, buf, op, go=True):
        """Start (or wait for) the copies of chunk ``c`` of slot ``s``
        into half ``buf`` of the double buffer: its live blocks only (none
        unless ``go``), one DMA per block and pool."""
        first = c * pages

        def body(j, carry):
            blk = tables_ref[s * max_blocks + first + j]
            dst = pl.ds(pl.multiple_of(j * bs, bs), bs)
            for hbm, vmem, sem in ((k_hbm, kbuf, sems.at[buf, 0]),
                                   (v_hbm, vbuf, sems.at[buf, 1])):
                copy = pltpu.make_async_copy(hbm.at[layer, blk],
                                             vmem.at[buf, :, dst], sem)
                copy.start() if op == "start" else copy.wait()
            return carry

        n = jnp.where(go, jnp.clip(n_live(s) - first, 0, pages), 0)
        jax.lax.fori_loop(jnp.int32(0), n, body, jnp.int32(0))

    def slot_body(s, buf):
        length = lengths_ref[s]
        nc = (n_live(s) + pages - 1) // pages

        def chunk_body(c, carry):
            buf, stats = carry
            nxt = 1 - buf
            # prefetch the next (slot, chunk) pair; none after the last
            last = c + 1 == nc
            ns = jnp.where(last, s + 1, s)
            dma(jnp.minimum(ns, n_slots - 1), jnp.where(last, 0, c + 1),
                nxt, "start", go=ns < n_slots)
            dma(s, c, buf, "wait")
            tok = c * width + jax.lax.broadcasted_iota(
                jnp.int32, (1, width), 1)
            row = c * width + jax.lax.broadcasted_iota(
                jnp.int32, (width, 1), 0)
            new = []
            for h in range(n_kv):
                m, l, acc = stats[h]
                q = q_ref[s, h]                             # (g, D)
                k = kbuf[buf, h]                            # (width, D)
                v = vbuf[buf, h]
                v = jnp.where(row < length, v, jnp.zeros((), v.dtype))
                sc = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                sc = jnp.where(tok < length, sc, NEG_INF)   # (g, width)
                m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
                p = jnp.exp(sc - m_new)
                corr = jnp.exp(m - m_new)
                new.append((m_new, l * corr + p.sum(axis=-1, keepdims=True),
                            acc * corr + jax.lax.dot_general(
                                p.astype(v.dtype), v,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)))
            return nxt, tuple(new)

        d = q_ref.shape[-1]
        init = tuple((jnp.full((g, 1), NEG_INF, jnp.float32),
                      jnp.zeros((g, 1), jnp.float32),
                      jnp.zeros((g, d), jnp.float32)) for _ in range(n_kv))
        buf, stats = jax.lax.fori_loop(jnp.int32(0), nc, chunk_body,
                                       (buf, init))
        for h in range(n_kv):
            _, l, acc = stats[h]
            o_ref[s, h] = (acc / l).astype(o_ref.dtype)
        return buf

    dma(0, 0, 0, "start")
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_slots), slot_body,
                      jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(q, k_pool, v_pool, tables, lengths, layer,
                           interpret: bool | None = None):
    """q: ``(n_slots, Hkv, g, D)`` (query head ``h * g + j`` at ``[:, h,
    j]``); k/v pools: ``(n_layers, n_pool_blocks, Hkv, block_size, D)``;
    tables: ``(n_slots, max_blocks)`` int32 pool block ids; lengths:
    ``(n_slots,)`` int32, each ``>= 1``; layer: int32 scalar.  Returns
    ``(n_slots, Hkv, g, D)`` in ``q.dtype``."""
    n_slots, n_kv, g, d = q.shape
    bs = k_pool.shape[3]
    max_blocks = tables.shape[1]
    pages = chunk_pages(bs, max_blocks)
    kernel = functools.partial(_paged_kernel, pages=pages,
                               max_blocks=max_blocks,
                               scale=1.0 / math.sqrt(d))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, n_kv, pages * bs, d), k_pool.dtype),
                pltpu.VMEM((2, n_kv, pages * bs, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=resolve_interpret(interpret),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      tables.reshape(-1).astype(jnp.int32), q, k_pool, v_pool)
