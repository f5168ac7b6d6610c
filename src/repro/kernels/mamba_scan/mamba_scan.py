"""Selective-scan (Mamba-1 recurrence) as a Pallas TPU kernel.

TPU adaptation (DESIGN.md §6): the recurrence is independent across channels,
so the grid tiles (batch × channel-block × time-chunk) and each instance
scans its time chunk sequentially with the (d_block, N) state held in VMEM
scratch — the state never round-trips HBM between chunks (time-chunk is the
innermost grid dim; Mosaic's revisiting rule keeps the scratch alive).
This replaces the GPU implementation's shared-memory parallel scan: on TPU
the VPU processes the (d_block, N) state tile per step while the sequential
time walk streams x/dt/B/C chunks HBM->VMEM.

Memory per instance: (3·lc·d_blk + 2·lc·N + d_blk·N) · 4 B — with the
default lc=256, d_blk=256, N=16 that is ~0.8 MB, far under VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                 y_ref, h_ref, h_scr, *, chunk: int, n_chunks: int):
    li = pl.program_id(2)

    @pl.when(li == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...]                                   # (d_blk, N)
    dvec = d_ref[0, :]                               # (d_blk,)

    def step(t, h):
        xt = x_ref[0, t, :]                          # (d_blk,)
        dtt = dt_ref[0, t, :]
        bt = b_ref[0, t, :]                          # (N,)
        ct = c_ref[0, t, :]
        da = jnp.exp(dtt[:, None] * a)               # (d_blk, N)
        h = da * h + (dtt * xt)[:, None] * bt[None, :]
        y = jnp.sum(h * ct[None, :], axis=1) + dvec * xt
        y_ref[0, t, :] = y
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, step, h_scr[...])

    @pl.when(li == n_chunks - 1)
    def _emit_state():
        h_ref[0, :, :] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("d_block", "chunk", "interpret"))
def mamba_scan_pallas(x, dt, Bt, Ct, A, D, d_block: int = 256,
                      chunk: int = 256, interpret: bool | None = None):
    """x/dt: (B, L, d) f32; Bt/Ct: (B, L, N) f32; A: (d, N); D: (d,).

    Returns (y (B, L, d), h_final (B, d, N)).
    """
    Bsz, L, d = x.shape
    N = A.shape[-1]
    d_block = min(d_block, d)
    chunk = min(chunk, L)
    assert d % d_block == 0 and L % chunk == 0, (d, L, d_block, chunk)
    nd, nl = d // d_block, L // chunk

    kernel = functools.partial(_scan_kernel, chunk=chunk, n_chunks=nl)
    grid = (Bsz, nd, nl)
    y, h = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b, di, li: (b, li, di)),
            pl.BlockSpec((1, chunk, d_block), lambda b, di, li: (b, li, di)),
            pl.BlockSpec((1, chunk, N), lambda b, di, li: (b, li, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, di, li: (b, li, 0)),
            pl.BlockSpec((d_block, N), lambda b, di, li: (di, 0)),
            pl.BlockSpec((1, d_block), lambda b, di, li: (0, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b, di, li: (b, li, di)),
            pl.BlockSpec((1, d_block, N), lambda b, di, li: (b, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, L, d), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, d, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d_block, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x.astype(jnp.float32), dt, Bt, Ct, A, D[None, :])
    return y, h
