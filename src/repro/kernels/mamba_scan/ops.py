"""Jitted public wrapper for the selective-scan Pallas kernel."""
from __future__ import annotations

import functools

import jax

from .mamba_scan import mamba_scan_pallas


@functools.partial(jax.jit, static_argnames=("d_block", "chunk", "interpret"))
def mamba_scan(x, dt, Bt, Ct, A, D, d_block: int = 256, chunk: int = 256,
               interpret: bool | None = None):
    """Selective scan.  See ``mamba_scan_pallas`` for shapes."""
    return mamba_scan_pallas(x, dt, Bt, Ct, A, D, d_block=d_block,
                             chunk=chunk, interpret=interpret)


def _dataflow_build(case: dict):
    """Abstract args for one kernelcheck case of ``mamba_scan_pallas``."""
    B, L, d, N = (case[k] for k in ("B", "L", "d", "N"))
    dt = case["dtype"]
    sds = jax.ShapeDtypeStruct
    x = sds((B, L, d), dt)
    bt = sds((B, L, N), dt)
    return (mamba_scan_pallas,
            (x, x, bt, bt, sds((d, N), dt), sds((d,), dt)), {})


def _make_dataflow():
    from ...analysis.dataflow import DataflowContract
    # Grid is (batch, channel block, time chunk): batch x channel
    # partition y/h; the time-chunk axis revisits them carrying the
    # (d_block, N) recurrence state in scratch (sequential).
    return DataflowContract(
        dimension_semantics=("parallel", "parallel", "sequential"),
        build=_dataflow_build)


DATAFLOW = _make_dataflow()
