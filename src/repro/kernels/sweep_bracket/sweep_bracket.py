"""Fused bracket-term + segment-sum Pallas TPU kernel for the scenario sweep.

The sweep's jax backend reduces the packed sample axis with a generic
scatter-add (``jax.ops.segment_sum``), which materializes every
``w * max(lat + delta, 0)`` bracket term at ``(n_scenarios, n_samples)`` in
HBM before reducing.  This kernel fuses the two: it tiles the
``(scenarios, packed_samples)`` plane, computes the three scenario-dependent
bracket variants of the access model (Eq. 6-10) inside the kernel —

  * ``hit_degraded``    Σ w · max(lat + Δ, 0)        over cache hits
  * ``lfb_mem``         Σ w · max(lat + Δ, 0)        over LFB samples
  * ``lfb_half``        Σ w · max(lat + Δ/2, 0)      over LFB samples
  * ``miss_congested``  Σ w · max(CXL_LAT, lat + Δ)  over DRAM misses

— and accumulates the per-site partial sums in VMEM scratch, so the bracket
intermediates never touch HBM.  The per-site reduction uses the per-sample
segment ids (``*_seg``) already carried by ``CompiledBundle``: each sample
tile builds a one-hot ``(block_n, n_seg)`` matrix from its ids and the
scatter becomes a ``(block_s, block_n) @ (block_n, n_seg)`` contraction on
the MXU (the canonical TPU segment-sum formulation — no data-dependent
stores).

The sample-block index is the *innermost* grid dimension, so the four VMEM
accumulators persist across the sample tiles of one scenario block (the
same Mosaic revisiting pattern as ``flash_attention``).

Padding convention (produced by ``CompiledBundle.padded_groups`` /
``ops.fused_bracket_segsum``): the three sample groups share one padded
length; padding rows carry ``w == 0`` (contributing exactly zero to any
bracket) and ``seg == 0`` (always in range).  Scenario rows and segment
columns are padded to tile multiples and sliced off by the wrapper.

``interpret`` defaults to the platform (``repro.kernels.resolve_interpret``):
compiled by Mosaic on TPU, where it runs in float32 (Mosaic has no f64);
interpreted elsewhere, where under ``enable_x64`` it runs in full float64
(how the sweep's parity bound of 1e-9 vs the NumPy backend is met).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

#: TPU tile multiples: last dim is always LANE-wide; the second-to-last is
#: SUBLANE for float32 (interpret mode does not care, but the layouts are
#: kept Mosaic-legal so the same kernel compiles on hardware).
LANE = 128
SUBLANE = 8


def _one_hot(seg, n_seg: int, dtype):
    """(block_n,) int32 ids -> (block_n, n_seg) one-hot in the compute dtype
    (2-D iota only — 1-D iota does not lower on TPU)."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (seg.shape[0], n_seg), 1)
    return (seg[:, None] == cols).astype(dtype)


def _scatter(term, hot):
    """(block_s, block_n) @ (block_n, n_seg) — the segment scatter as an MXU
    contraction, accumulated in the term dtype.  ``HIGHEST`` keeps f32 terms
    at f32 accuracy on the MXU (the default single bf16 pass would cost
    about three decimal digits of the sweep's 1e-5 f32 bound)."""
    return jax.lax.dot_general(term, hot, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=term.dtype)


def _bracket_kernel(hl_ref, hw_ref, hs_ref, ll_ref, lw_ref, ls_ref,
                    ml_ref, mw_ref, ms_ref, delta_ref, cxl_ref,
                    hit_o, lmem_o, lhalf_o, mcong_o,
                    hit_a, lmem_a, lhalf_a, mcong_a, *,
                    n_seg_pad: int, n_blocks: int):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        for acc in (hit_a, lmem_a, lhalf_a, mcong_a):
            acc[...] = jnp.zeros_like(acc)

    d = delta_ref[...]            # (block_s, 1): CXL_LAT - MEM_LAT
    cxl = cxl_ref[...]            # (block_s, 1)
    dt = d.dtype

    # hits: degrade to memory-origin timing, floored at zero
    lat, w = hl_ref[0, :], hw_ref[0, :]
    hot = _one_hot(hs_ref[0, :], n_seg_pad, dt)
    hit_a[...] += _scatter(w[None, :] * jnp.maximum(lat[None, :] + d, 0.0),
                           hot)

    # LFB: both brackets share the samples and the one-hot
    lat, w = ll_ref[0, :], lw_ref[0, :]
    hot = _one_hot(ls_ref[0, :], n_seg_pad, dt)
    lmem_a[...] += _scatter(w[None, :] * jnp.maximum(lat[None, :] + d, 0.0),
                            hot)
    lhalf_a[...] += _scatter(
        w[None, :] * jnp.maximum(lat[None, :] + d / 2.0, 0.0), hot)

    # DRAM misses: congested bracket, floored at the flat CXL latency
    lat, w = ml_ref[0, :], mw_ref[0, :]
    hot = _one_hot(ms_ref[0, :], n_seg_pad, dt)
    mcong_a[...] += _scatter(
        w[None, :] * jnp.maximum(cxl, lat[None, :] + d), hot)

    @pl.when(ni == n_blocks - 1)
    def _emit():
        hit_o[...] = hit_a[...]
        lmem_o[...] = lmem_a[...]
        lhalf_o[...] = lhalf_a[...]
        mcong_o[...] = mcong_a[...]


def bracket_segsum_padded(hit, lfb, miss, delta, cxl_lat, n_seg_pad: int, *,
                          block_s: int, block_n: int,
                          interpret: bool | None = None):
    """Raw ``pl.pallas_call`` over pre-padded operands.

    ``hit``/``lfb``/``miss``: ``(lat, w, seg)`` triples, each ``(1, n_pad)``
    with ``seg`` int32; ``delta``/``cxl_lat``: ``(s_pad, 1)``.  ``n_pad`` /
    ``s_pad`` must be multiples of ``block_n`` / ``block_s`` and ``n_seg_pad``
    a LANE multiple — ``ops.fused_bracket_segsum`` handles the padding.

    Returns the four ``(s_pad, n_seg_pad)`` matrices in kernel order
    (hit_degraded, lfb_mem, lfb_half, miss_congested).
    """
    s_pad = delta.shape[0]
    n_pad = hit[0].shape[-1]
    grid = (s_pad // block_s, n_pad // block_n)

    sample = pl.BlockSpec((1, block_n), lambda si, ni: (0, ni))
    scen = pl.BlockSpec((block_s, 1), lambda si, ni: (si, 0))
    out = pl.BlockSpec((block_s, n_seg_pad), lambda si, ni: (si, 0))
    acc = pltpu.VMEM((block_s, n_seg_pad), delta.dtype)

    kernel = functools.partial(_bracket_kernel, n_seg_pad=n_seg_pad,
                               n_blocks=grid[1])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[sample] * 9 + [scen, scen],
        out_specs=[out] * 4,
        out_shape=[jax.ShapeDtypeStruct((s_pad, n_seg_pad), delta.dtype)] * 4,
        scratch_shapes=[acc] * 4,
        interpret=resolve_interpret(interpret),
    )(*hit, *lfb, *miss, delta, cxl_lat)


# --------------------------------------------------------------------------
# Generic tiled segment sum (the non-fused slot-in behind
# ``sweep_kernel._segment_sum``)
# --------------------------------------------------------------------------

def _segsum_kernel(x_ref, seg_ref, o_ref, acc, *, n_seg_pad: int,
                   n_blocks: int):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]                                    # (block_r, block_n)
    acc[...] += _scatter(x, _one_hot(seg_ref[0, :], n_seg_pad, x.dtype))

    @pl.when(ni == n_blocks - 1)
    def _emit():
        o_ref[...] = acc[...]


def segsum_padded(x, seg, n_seg_pad: int, *, block_r: int, block_n: int,
                  interpret: bool | None = None):
    """Raw tiled segment sum: ``x (r_pad, n_pad)`` + ``seg (1, n_pad)`` int32
    -> ``(r_pad, n_seg_pad)``.  Same padding contract as
    :func:`bracket_segsum_padded` (zero-padded ``x``, id-0 padded ``seg``)."""
    r_pad, n_pad = x.shape
    grid = (r_pad // block_r, n_pad // block_n)
    kernel = functools.partial(_segsum_kernel, n_seg_pad=n_seg_pad,
                               n_blocks=grid[1])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_r, block_n), lambda ri, ni: (ri, ni)),
                  pl.BlockSpec((1, block_n), lambda ri, ni: (0, ni))],
        out_specs=pl.BlockSpec((block_r, n_seg_pad), lambda ri, ni: (ri, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, n_seg_pad), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_r, n_seg_pad), x.dtype)],
        interpret=resolve_interpret(interpret),
    )(x, seg)
