"""Jitted public wrapper for the paged decode-attention Pallas kernel."""
from __future__ import annotations

import functools

import jax

from .paged_attention import paged_attention_pallas


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pool, v_pool, tables, lengths, layer,
                    interpret: bool | None = None):
    """One query token per slot against its paged cache.

    q: ``(n_slots, Hkv, g, D)`` (query head ``h * g + j`` at ``[:, h,
    j]``); k/v pools: ``(n_layers, n_pool_blocks, Hkv, block_size, D)``;
    tables: ``(n_slots, max_blocks)`` pool block ids; lengths: ``(n_slots,)``
    positions each slot attends (``pos + 1``, at least 1); layer: which
    layer of the stacked pools.  Only blocks below ``ceil(length /
    block_size)`` are read.  ``interpret=None`` compiles the kernel on TPU
    and interprets it elsewhere (``repro.kernels.resolve_interpret``).
    """
    return paged_attention_pallas(q, k_pool, v_pool, tables, lengths, layer,
                                  interpret=interpret)
