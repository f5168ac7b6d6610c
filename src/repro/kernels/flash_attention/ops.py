"""Jitted public wrapper for the flash-attention Pallas kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_bhsd


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """q: (B, S, Hq, D); k/v: (B, T, Hkv, D) -> (B, S, Hq, D).

    ``interpret=None`` compiles the kernel on TPU and interprets it
    elsewhere (``repro.kernels.resolve_interpret``).
    """
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    qh = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    kh = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)
    vh = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)
    out = flash_attention_bhsd(qh, kh, vh, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret)
    return out.reshape(B, Hq, S, D).transpose(0, 2, 1, 3)


def _dataflow_build(case: dict):
    """Abstract head-major args for one kernelcheck case (the dataflow
    tier traces ``flash_attention_bhsd`` itself — the public wrapper only
    adds the layout transposes, which carry no block geometry)."""
    B, S, Hq, Hkv, T, D = (case[k] for k in ("B", "S", "Hq", "Hkv",
                                             "T", "D"))
    dt = case["dtype"]
    sds = jax.ShapeDtypeStruct
    q = sds((B * Hq, S, D), dt)
    kv = sds((B * Hkv, T, D), dt)
    return flash_attention_bhsd, (q, kv, kv), {"causal": True}


def _make_dataflow():
    from ...analysis.dataflow import DataflowContract
    # Grid is (kv head, group, q block, kv block): the first three
    # partition the output; the kv-block axis revisits each output block
    # carrying the online-softmax state in scratch (sequential).
    return DataflowContract(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "sequential"),
        build=_dataflow_build)


DATAFLOW = _make_dataflow()
