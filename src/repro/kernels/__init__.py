"""Pallas TPU kernels for the compute hot spots (DESIGN.md §6).

Each kernel package ships ``<name>.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jitted public wrapper) and ``ref.py`` (pure-jnp oracle).
Every ``interpret`` argument defaults to ``None``, which
:func:`resolve_interpret` turns into the platform's mode: the compiled
Mosaic kernel on TPU, the Pallas interpreter elsewhere (how the CPU test
suite runs the real kernel bodies against the oracles).

  flash_attention/  blockwise online-softmax attention (GQA, causal)
  paged_attention/  single-token decode attention straight from a paged
                    KV pool through the block table (live blocks only)
  mamba_scan/       selective-scan recurrence (channel-blocked, VMEM state)
  halo_exchange/    message-free ring exchange via async remote DMA +
                    semaphore handshake — the paper's mechanism as a kernel
  sweep_bracket/    fused bracket-term + per-site segment sum for the
                    scenario sweep (the ``backend="pallas"`` executor)
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``interpret`` as given, or (``None``) whether the default backend
    lacks a TPU and so must interpret the kernel instead of compiling it."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
