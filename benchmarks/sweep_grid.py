"""Fig. 7 sensitivity band as a 2-D scenario grid (sweep-engine section).

The paper quotes two multinode calibration points: CXL_LAT/ATOMIC =
350/430 ns (~1.37x replacing ALL halos) and the optimistic 300/350 ns
(~1.59x).  Those are two samples of a whole design space — the related
CXL measurements put pooled-memory latency anywhere in a 2-3x band.  The
sweep engine prices the entire (cxl_lat_ns x cxl_atomic_lat_ns) grid in
one pass over the same multinode stencil bundle, turning the two-point
claim into the full sensitivity surface.

This section also IS the sweep's perf benchmark AND the CI smoke for the
``price()`` front door: it drives every REGISTERED backend
(``known_backends()`` — numpy, jax.jit, the fused Pallas
bracket/segment-sum kernel in interpret mode, the streaming distributed
top-k reducer, plus anything a plugin registered) through
``price(cb, grid, plan=ExecPlan(backend))``, times
each against the scalar ``predict_run`` loop, prices one
``ParamGrid.sample`` Latin-hypercube set on top of the factorial grid,
and writes the numbers to ``BENCH_sweep.json`` so the perf trajectory is
tracked across PRs.  (Interpret-mode Pallas runs the kernel body in
Python, so its wall time measures correctness-mode cost, not TPU speed —
the point is that the REAL kernel runs in CI.)

Usage:  PYTHONPATH=src python -m benchmarks.sweep_grid [--quick]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.apps.stencil.spec import HALO_CALLS, StencilConfig, build_spec
from repro.core import (ExecPlan, ModelParams, ParamGrid, SweepAggregates,
                        TraceBundle, compile_bundle, is_streaming,
                        known_backends, predict_run, price)
from repro.memsim.hooks import collect
from repro.memsim.machine import NetworkParams

LAT_GRID = (250.0, 300.0, 350.0, 400.0, 450.0, 500.0, 600.0, 700.0)
ATOMIC_GRID = (300.0, 350.0, 430.0, 500.0, 600.0, 653.0, 700.0, 800.0)
PAPER_POINTS = {(350.0, 430.0): "paper default (~1.37x)",
                (300.0, 350.0): "paper optimistic (~1.59x)"}
BENCH_JSON = "BENCH_sweep.json"


def _multinode_bundle(tile: int, seed: int = 0):
    cfg = StencilConfig(tile=tile, grid=(8, 8), ranks_per_socket=6)
    return collect(build_spec(cfg), network=NetworkParams.multinode(),
                   seed=seed, bw_share=cfg.bw_share,
                   ranks_per_socket=cfg.ranks_per_socket)


def _best_of(fn, n: int = 3) -> float:
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _max_rel(a: np.ndarray, b: np.ndarray) -> float:
    """Max elementwise relative error of ``a`` vs reference ``b``."""
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))


def run(quick: bool = False, tile: int = 32, json_path: str = BENCH_JSON,
        trace: str | None = None):
    # tile=32 is where the paper's headline ALL-halo speedups live (Fig. 7
    # peaks at the smallest tile; our scalar fig7 section reproduces
    # 1.274x/1.505x there) — the grid shows the full latency band around it.
    lats = LAT_GRID[::2] if quick else LAT_GRID
    atomics = ATOMIC_GRID[::2] if quick else ATOMIC_GRID
    if trace is not None:
        tdir = Path(trace)
        if not (tdir / "meta.json").is_file():
            raise SystemExit(
                f"error: trace bundle not found: {tdir} "
                "(expected a TraceBundle.save directory containing "
                "meta.json)")
        bundle = TraceBundle.load(tdir)
        replaced = None          # price every recorded call-site
        label = f"trace={trace}"
    else:
        bundle = _multinode_bundle(tile)
        replaced = set(HALO_CALLS)
        label = f"ALL-halo, tile={tile}"
    cb = compile_bundle(bundle)
    grid = ParamGrid.product(ModelParams.multinode(),
                             cxl_lat_ns=list(lats),
                             cxl_atomic_lat_ns=list(atomics))

    res = price(cb, grid)
    speed = res.predicted_speedup(replaced=replaced) \
        .reshape(len(lats), len(atomics))

    print(f"predicted speedup, {label} "
          f"({len(grid)} scenarios in one pass)")
    header = "cxl_lat_ns \\ atomic_ns " + " ".join(f"{a:7.0f}" for a in atomics)
    print(header)
    for i, lat in enumerate(lats):
        row = " ".join(f"{speed[i, j]:7.3f}" for j in range(len(atomics)))
        print(f"{lat:22.0f} {row}")
    for (lat, atom), claim in PAPER_POINTS.items():
        if trace is None and lat in lats and atom in atomics:
            s = speed[lats.index(lat), atomics.index(atom)]
            print(f"claim,{claim},{s:.3f}")

    # sensitivity band: the spread the latency uncertainty induces
    print(f"band,min_speedup,{speed.min():.3f},max_speedup,{speed.max():.3f}")

    # ---- price() on EVERY registered backend -> BENCH_sweep.json -----------
    # parity bound per backend: numpy is the bit-exact reference; jax
    # reorders the segment sums (1e-6 acceptance); anything else (pallas,
    # plugins) is held to the 1e-9 f64 bound.
    S = len(grid)
    chunk = max(1, S // 4)
    backends = {}
    rel_errs = {}

    t_numpy = _best_of(lambda: price(cb, grid))
    backends["numpy"] = {"wall_s": t_numpy, "scenarios_per_s": S / t_numpy}

    chunk_plan = ExecPlan(chunk_scenarios=chunk)
    t_chunked = _best_of(lambda: price(cb, grid, plan=chunk_plan))
    backends["numpy_chunked"] = {"wall_s": t_chunked,
                                 "scenarios_per_s": S / t_chunked,
                                 "chunk_scenarios": chunk}

    res_chunked = price(cb, grid, plan=chunk_plan)
    assert np.array_equal(res_chunked.gain_ns, res.gain_ns), \
        "chunked numpy must be bit-identical"

    for name in known_backends():
        if name == "numpy":
            continue
        plan = ExecPlan(backend=name, topk=min(64, S)) if is_streaming(name) \
            else ExecPlan(backend=name)
        t0 = time.perf_counter()
        res_b = price(cb, grid, plan=plan)       # includes any jit compile
        t_cold = time.perf_counter() - t0
        t_b = _best_of(lambda: price(cb, grid, plan=plan))
        backends[name] = {"wall_s": t_b, "scenarios_per_s": S / t_b,
                          "compile_s": t_cold - t_b,
                          "plan": plan.to_string()}
        if name == "pallas":
            backends[name]["interpret"] = res_b.plan.pallas_interpret
        if is_streaming(name):
            # streaming reducers return top-k rows + exact aggregates, not
            # matrices: pin the surviving rows against the numpy reference
            # and every aggregate against its matrix-path recomputation
            backends[name]["topk"] = plan.topk
            backends[name]["shard_rows"] = res_b.shard_rows
            agg, ragg = res_b.aggregates, SweepAggregates.from_result(res)
            assert agg.count == ragg.count \
                and np.array_equal(agg.hist, ragg.hist) \
                and np.array_equal(agg.n_beneficial, ragg.n_beneficial), \
                f"{name} streaming aggregates diverged from numpy"
            rel_errs[name] = max(
                _max_rel(res_b.result.gain_ns, res.gain_ns[res_b.indices]),
                _max_rel(res_b.speedups,
                         res.predicted_speedup()[res_b.indices]),
                _max_rel(np.array([agg.speedup_mean, agg.speedup_min,
                                   agg.speedup_max]),
                         np.array([ragg.speedup_mean, ragg.speedup_min,
                                   ragg.speedup_max])),
                _max_rel(agg.gain_sum, ragg.gain_sum))
        else:
            rel_errs[name] = _max_rel(res_b.gain_ns, res.gain_ns)
        bound = 1e-6 if name == "jax" else 1e-9
        assert rel_errs[name] < bound, \
            f"{name} backend drifted from numpy: {rel_errs[name]}"

    # ---- one ParamGrid.sample set through the same front door ---------------
    n_sample = 8 if quick else 32
    sampled = ParamGrid.sample(ModelParams.multinode(), n_sample, seed=0,
                               cxl_lat_ns=(min(lats), max(lats)),
                               cxl_atomic_lat_ns=(min(atomics), max(atomics)))
    res_sam = price(cb, sampled)
    sam_jax = price(cb, sampled, plan=ExecPlan("jax"))
    sam_rel = _max_rel(sam_jax.gain_ns, res_sam.gain_ns)
    assert sam_rel < 1e-6, f"sampled set drifted across backends: {sam_rel}"
    s_sam = res_sam.predicted_speedup(replaced=replaced)
    print(f"sample,{n_sample} LHS points,band,{s_sam.min():.3f},"
          f"{s_sam.max():.3f}")

    # scalar predict_run loop — the pre-sweep baseline
    t_loop = _best_of(lambda: [predict_run(bundle, p) for p in grid.params])
    print(f"perf,scalar_loop_ms,{t_loop * 1e3:.1f},sweep_ms,"
          f"{t_numpy * 1e3:.2f},speedup,{t_loop / max(t_numpy, 1e-9):.0f}x")
    for name, row in backends.items():
        print(f"perf,{name},wall_ms,{row['wall_s'] * 1e3:.2f},"
              f"scenarios_per_s,{row['scenarios_per_s']:.0f}")

    bench = {
        "benchmark": "sweep_grid",
        "quick": bool(quick),
        "tile": tile,
        "grid_size": S,
        "n_calls": cb.n_calls,
        "registered_backends": list(known_backends()),
        "jax_numpy_max_rel_err": rel_errs.get("jax"),
        "pallas_numpy_max_rel_err": rel_errs.get("pallas"),
        "distributed_numpy_max_rel_err": rel_errs.get("distributed"),
        "backend_max_rel_err": rel_errs,
        "sample_points": n_sample,
        "sample_speedup_band": [float(s_sam.min()), float(s_sam.max())],
        "scalar_loop_s": t_loop,
        "backends": backends,
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(bench, f, indent=2)
        print(f"wrote {json_path}")
    return speed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--tile", type=int, default=32)
    ap.add_argument("--json", default=BENCH_JSON,
                    help="output path for the machine-readable benchmark "
                         "record ('' disables)")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="price a saved TraceBundle directory instead of "
                         "the built-in stencil bundle (all call-sites "
                         "replaced)")
    args = ap.parse_args(argv)
    run(quick=args.quick, tile=args.tile, json_path=args.json,
        trace=args.trace)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
