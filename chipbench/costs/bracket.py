"""Work of the fused bracket segment-sum kernel, from its shapes.

Per scenario and packed sample, the kernel evaluates the scenario-
dependent bracket terms of Eq. 6-10 and adds each to its call-site's
sum: a hit term ``w * max(lat + d, 0)`` (add, max, multiply, accumulate:
4 operations), two LFB terms (the same 4 each, plus the halving of ``d``:
9) and a miss term ``w * max(cxl, lat + d)`` (4).  Only the samples the
bundles hold count, not the padding, nor the one-hot matrix products the
kernel uses to scatter the sums.  Bytes are what must cross HBM in
float32: the two per-scenario inputs, the packed samples (latency, weight,
segment id) and the four ``(scenarios, call-sites)`` outputs.
"""
from __future__ import annotations

F32 = 4


def bracket_ops(n_scen: int, n_hit: int, n_lfb: int, n_miss: int) -> float:
    return float(n_scen) * (4 * n_hit + 9 * n_lfb + 4 * n_miss)


def bracket_bytes(n_scen: int, n_hit: int, n_lfb: int, n_miss: int,
                  n_calls: int) -> float:
    samples = 3 * (n_hit + n_lfb + n_miss)
    return float(F32 * (2 * n_scen + samples + 4 * n_scen * n_calls))
