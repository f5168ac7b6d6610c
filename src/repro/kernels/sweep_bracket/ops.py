"""Jitted public wrappers for the fused bracket segment-sum kernel.

These own the padding/unpadding around the raw ``pallas_call``s in
``sweep_bracket.py``: sample axes to ``block_n`` multiples (zero-weight /
zero-value rows, segment id 0), the scenario/row axis to ``block_s``
multiples, and the segment axis to a LANE multiple.  Results are sliced
back to the caller's true shapes, so callers never see the tile geometry.

``CompiledBundle.padded_groups()`` produces the shared-length group layout
these wrappers consume; arbitrary per-group lengths are also accepted and
aligned here (the pads fold into the jit trace — bundle arrays are closed
over as constants by the sweep executor).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .sweep_bracket import (LANE, SUBLANE, bracket_segsum_padded,
                            segsum_padded)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sample_tiling(n: int, block_n: int) -> tuple:
    """Pad the sample axis only to a LANE multiple, then pick a block size
    that divides it (falling back to one LANE) — padding straight to a
    ``block_n`` multiple would waste up to ~2x compute on zero rows for
    counts just past a block boundary (e.g. 640 -> 1024)."""
    n_pad = _round_up(max(n, 1), LANE)
    block_n = min(block_n, n_pad)
    if n_pad % block_n:
        block_n = LANE
    return n_pad, block_n


def _pad_group(group, n_pad: int):
    """(lat, w, seg) -> (1, n_pad)-shaped, zero/id-0 padded triple."""
    lat, w, seg = (jnp.asarray(a) for a in group)
    k = n_pad - lat.shape[-1]
    return (jnp.pad(lat, (0, k)).reshape(1, n_pad),
            jnp.pad(w, (0, k)).reshape(1, n_pad),
            jnp.pad(seg.astype(jnp.int32), (0, k)).reshape(1, n_pad))


@functools.partial(jax.jit, static_argnames=("n_seg", "block_s", "block_n",
                                             "interpret"))
def fused_bracket_segsum(hit, lfb, miss, delta, cxl_lat, n_seg: int, *,
                         block_s: int = SUBLANE, block_n: int = 512,
                         interpret: bool | None = None) -> dict:
    """The four scenario-dependent bracket aggregates, fused.

    ``hit`` / ``lfb`` / ``miss``: ``(lat, w, seg)`` packed sample triples
    (1-D, any lengths — zero-``w`` padding is applied here); ``delta`` /
    ``cxl_lat``: per-scenario ``(S,)`` or ``(S, 1)``; ``n_seg``: number of
    call-sites.  Returns ``{name: (S, n_seg)}`` for ``hit_degraded``,
    ``lfb_mem``, ``lfb_half`` and ``miss_congested`` in the input dtype
    (float64 under ``enable_x64`` — the sweep's parity mode).
    """
    delta = jnp.asarray(delta).reshape(-1, 1)
    cxl_lat = jnp.asarray(cxl_lat).reshape(-1, 1)
    s = delta.shape[0]
    names = ("hit_degraded", "lfb_mem", "lfb_half", "miss_congested")
    if s == 0 or n_seg == 0:
        return {k: jnp.zeros((s, n_seg), delta.dtype) for k in names}

    n_max = max(g[0].shape[-1] for g in (hit, lfb, miss))
    n_pad, block_n = _sample_tiling(n_max, block_n)
    block_s = min(block_s, _round_up(s, SUBLANE))
    s_pad = _round_up(s, block_s)
    n_seg_pad = _round_up(n_seg, LANE)

    pad_s = ((0, s_pad - s), (0, 0))
    outs = bracket_segsum_padded(
        _pad_group(hit, n_pad), _pad_group(lfb, n_pad),
        _pad_group(miss, n_pad),
        jnp.pad(delta, pad_s), jnp.pad(cxl_lat, pad_s),
        n_seg_pad, block_s=block_s, block_n=block_n, interpret=interpret)
    return {k: v[:s, :n_seg] for k, v in zip(names, outs)}


def _dataflow_build(case: dict):
    """Abstract args for one kernelcheck case of ``fused_bracket_segsum``
    (the dataflow tier traces the wrapper under ``jax.eval_shape``)."""
    sds = jax.ShapeDtypeStruct
    dt = case["dtype"]
    group = tuple(sds((case["n_max"],), dt if i < 2 else "int32")
                  for i in range(3))
    scen = sds((case["S"],), dt)
    return (fused_bracket_segsum, (group, group, group, scen, scen),
            {"n_seg": case["n_seg"]})


def _make_dataflow():
    from ...analysis.dataflow import DataflowContract
    # Grid is (scenario block, sample block): scenario rows partition the
    # outputs (parallel); the sample axis revisits each output block to
    # accumulate partial segment sums (sequential, scratch-carried).
    return DataflowContract(dimension_semantics=("parallel", "sequential"),
                            build=_dataflow_build)


DATAFLOW = _make_dataflow()


@functools.partial(jax.jit, static_argnames=("n_seg", "block_r", "block_n",
                                             "interpret"))
def segment_sum_pallas(x, seg_ids, n_seg: int, *, block_r: int = SUBLANE,
                       block_n: int = 512, interpret: bool | None = None):
    """Tiled Pallas segment sum: ``x (..., n)`` + sorted-or-not ``seg_ids
    (n,)`` -> ``(..., n_seg)``.  Drop-in for the jax branch of
    ``sweep_kernel._segment_sum`` (empty segments sum to zero; ids are
    assumed in ``[0, n_seg)``)."""
    x = jnp.asarray(x)
    seg_ids = jnp.asarray(seg_ids, jnp.int32)
    lead, n = x.shape[:-1], x.shape[-1]
    rows = int(np.prod(lead, dtype=np.int64)) if lead else 1
    if n == 0 or n_seg == 0 or rows == 0:
        return jnp.zeros(lead + (n_seg,), x.dtype)

    n_pad, block_n = _sample_tiling(n, block_n)
    block_r = min(block_r, _round_up(rows, SUBLANE))
    r_pad = _round_up(rows, block_r)
    xp = jnp.pad(x.reshape(rows, n), ((0, r_pad - rows), (0, n_pad - n)))
    segp = jnp.pad(seg_ids, (0, n_pad - n)).reshape(1, n_pad)

    out = segsum_padded(xp, segp, _round_up(n_seg, LANE), block_r=block_r,
                        block_n=block_n, interpret=interpret)
    return out[:rows, :n_seg].reshape(lead + (n_seg,))
