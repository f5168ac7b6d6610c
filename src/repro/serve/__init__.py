"""Serving substrate: batched prefill/decode with KV + SSM caches.

Two engines: the static-batch ``ServeEngine`` (one prefill, one decode
loop, batch ends together; also the multimodal path and the greedy oracle
the continuous engine is tested against), and the continuous-batching
``PagedContinuousEngine`` (fixed decode slots fed from a request queue,
block/paged KV from a shared pool via a block table, chunked prefill
admission, eos/length retirement with block free/reuse, occupancy
telemetry in ``ServeStats``).
"""
from .engine import ServeEngine, sample_logits
from .paged import (BlockPool, PagedContinuousEngine, PoolExhausted, Request,
                    ServeStats)

__all__ = ["ServeEngine", "sample_logits", "PagedContinuousEngine",
           "BlockPool", "PoolExhausted", "Request", "ServeStats"]
