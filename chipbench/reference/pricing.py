"""Plain float64 reference of the paper's pricing model (arXiv 2512.08005).

Written from the model's equations, site by site, with NumPy only: the
workload characterization (Sec. IV-B, Eq. 3-4 and the Compute remainder
rule), the access model's per-category brackets (Eq. 5-10, with the
prefetched-hit split and the HPCG unpack blend), and the Hockney, LogGP
and two-atomic transfer models (Eq. 1-2).  It reads the recorded trace
bundles (samples, receives, counters) and the scenario columns, and
nothing the pricing engine computes from them.

``rnd`` rounds every intermediate array: ``None`` keeps float64; the
control passes a rounding to bfloat16 to price in that precision.
"""
from __future__ import annotations

import numpy as np

LINE = 64
CATS = ("mbw", "mlat", "cbw", "clat", "compute")


def _ramp(v, lo, hi):
    """Eq. 3: 0 below ``lo``, 1 above ``hi``, quadratic between."""
    t = min(1.0, max(0.0, (v - lo) / (hi - lo)))
    return t * t


def _normalize(w: dict, cats, cmax: float) -> dict:
    """Weights summing to 1, Compute taking the remainder up to ``cmax``
    and the rest of the remainder split over ``cats``."""
    w = {c: max(0.0, w[c]) for c in cats}
    s = sum(w.values())
    out = dict.fromkeys(CATS, 0.0)
    if s >= 1.0:
        out.update({c: w[c] / s for c in cats})
        return out
    rem = 1.0 - s
    out["compute"] = min(rem, cmax)
    out.update({c: w[c] + (rem - out["compute"]) / len(cats) for c in cats})
    return out


def characterize(counters, m: dict) -> tuple:
    """(first-load weights, subsequent-load weights) of one run."""
    wall = max(counters.wall_time_ns, 1e-9)
    lds = max(counters.ld_ins, 1.0)
    thr = m["thresholds"]
    mbw = _ramp(counters.imc_reads * LINE / wall / m["peak_mem_bw_Bpns"],
                *thr["mbw"])
    mlat = max(0.0, _ramp(counters.l3_ldm / lds, *thr["mlat"]) - mbw)
    cbw = max(_ramp(counters.ld_ins * m["avg_load_bytes"] / wall
                    / m["l1_bw_Bpns"], *thr["cbw"]),
              _ramp(counters.l1_ldm * LINE / wall / m["l2_bw_Bpns"],
                    *thr["cbw"]))
    clat = max(0.0, _ramp(counters.l1_ldm / lds, *thr["clat"])
               - (mbw + mlat + cbw))
    raw = {"mbw": mbw, "mlat": mlat, "cbw": cbw, "clat": clat}
    first = _normalize(raw, ("mbw", "mlat"), m["compute_max_weight"])
    sub = _normalize(raw, ("mbw", "mlat", "cbw", "clat"),
                     m["compute_max_weight"])
    return first, sub


def price_bundle(bundle, cxl_lat, cxl_atomic, mpi_code, m: dict,
                 rnd=None) -> dict:
    """Per-site component times of one bundle under ``S`` scenarios.

    ``cxl_lat`` / ``cxl_atomic``: ``(S,)`` ns; ``mpi_code``: ``(S,)``
    index into ``("hockney", "loggp")``.  Returns ``{field: (S, n_sites)}``
    for the four component matrices and ``"speedup": (S,)``.
    """
    r = rnd or (lambda x: x)
    cxl = r(np.asarray(cxl_lat, np.float64))
    atomic = r(np.asarray(cxl_atomic, np.float64))
    delta = r(cxl - m["mem_lat_ns"])
    lpf = {"mbw": m["lpf_bw"], "mlat": m["lpf_lat"], "cbw": m["lpf_bw"],
           "clat": m["lpf_lat"], "compute": m["lpf_bw"]}
    first, sub = characterize(bundle.counters, m)
    cols = {k: [] for k in ("t_transfer_mpi_ns", "t_transfer_cxl_ns",
                            "t_access_mpi_ns", "t_access_cxl_ns")}
    gain = np.zeros_like(cxl)
    period = float(bundle.sampling_period)
    for site in bundle.call_sites.values():
        lat = np.array([s.lat_ns for s in site.samples], np.float64)
        w = np.array([s.weight for s in site.samples], np.float64)
        src = [s.source.value for s in site.samples]
        hit = np.array([x in ("L1", "L2", "L3") for x in src], bool)
        lfb = np.array([x == "LFB" for x in src], bool)
        miss = np.array([x == "DRAM" for x in src], bool)

        def wsum(mask, term):           # (S, k) terms -> (S,)
            return r(r(w[mask] * term).sum(axis=-1))

        d = delta[:, None]
        t_hit = float(np.sum(w[hit] * lat[hit]))
        t_lfb = float(np.sum(w[lfb] * lat[lfb]))
        hit_deg = wsum(hit, r(np.maximum(r(lat[hit] + d), 0.0)))
        lfb_mem = wsum(lfb, r(np.maximum(r(lat[lfb] + d), 0.0)))
        lfb_half = wsum(lfb, r(np.maximum(r(lat[lfb] + r(d / 2.0)), 0.0)))
        miss_flat = r(float(np.sum(w[miss])) * cxl)
        miss_cong = wsum(miss, r(np.maximum(cxl[:, None],
                                            r(lat[miss] + d))))
        pf = min(1.0, 1.0 / max(1.0, site.loads_per_line))
        hit_split = r(r((1.0 - pf) * t_hit) + r(pf * hit_deg))
        bracket = {
            "mlat": r(r(t_hit + lfb_mem) + miss_flat),            # Eq. 6
            "mbw": r(r(hit_split + lfb_mem) + miss_cong),         # Eq. 7
            "cbw": r(r(hit_split + t_lfb) + miss_cong),           # Eq. 8
            "clat": r(r(t_hit + t_lfb) + miss_flat),              # Eq. 9
            "compute": r(r(t_hit + lfb_half) + miss_flat),        # Eq. 10
        }
        f = 1.0 / max(1.0, site.accesses_per_element)
        wts = {c: f * first[c] + (1.0 - f) * sub[c] for c in CATS}
        t_cxl = sum(r(wts[c] * bracket[c] / lpf[c]) for c in CATS)
        t_ddr = sum(wts[c] * float(np.sum(w * lat)) / lpf[c] for c in CATS)
        if site.unpack:
            t_cxl = r(f * t_cxl + (1.0 - f) * t_ddr)
        acc_mpi = np.full_like(cxl, t_ddr * period)
        acc_cxl = r(t_cxl * period)

        n = float(sum(c.count for c in site.comms))
        total = float(sum(c.count * c.bytes for c in site.comms))
        gap = float(sum(c.count * max(0, c.bytes - 1) for c in site.comms))
        hockney = n * m["mpi_lat_ns"] + total / m["mpi_bw_Bpns"]
        loggp = n * m["mpi_lat_ns"] + gap / m["mpi_bw_Bpns"]
        tr_mpi = np.where(mpi_code == 1, loggp, hockney).astype(np.float64)
        tr_cxl = r(2.0 * atomic * n)

        for k, v in (("t_transfer_mpi_ns", tr_mpi),
                     ("t_transfer_cxl_ns", tr_cxl),
                     ("t_access_mpi_ns", acc_mpi),
                     ("t_access_cxl_ns", acc_cxl)):
            cols[k].append(v)
        gain = r(gain + r(r(tr_mpi + acc_mpi) - r(tr_cxl + acc_cxl)))
    out = {k: np.stack(v, axis=1) for k, v in cols.items()}
    base = float(bundle.counters.wall_time_ns)
    out["speedup"] = r(base / r(base - gain))
    return out


def bf16_round(x):
    """Round to bfloat16 and back: the control's precision."""
    import ml_dtypes
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)
