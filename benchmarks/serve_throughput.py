"""Serving throughput smoke: static vs continuous engines on a reduced arch.

Times steady-state generation (compile excluded via a warmup run) for the
static and continuous engines on the same request set, plus a staggered
arrival workload only the continuous scheduler can keep slots busy for.
With ``--paged`` the continuous sections run the block/paged-KV engine
instead (greedy parity with the static engine is asserted either way) and
the JSON gains ``kv_bytes_peak`` / ``kv_bytes_dense``.  A seeded Poisson
load-generator run then reports deployment SLO numbers (p50/p99 latency,
TTFT, sustained tok/s, SLO attainment), and the engine's OBSERVED step mix
weights the CXL-scenario pricing (``predicted_speedup(weights=engine)``).
Everything lands in ``BENCH_serve.json`` so the serving perf trajectory is
tracked across PRs alongside ``BENCH_sweep.json``.

Usage:  PYTHONPATH=src python -m benchmarks.serve_throughput \
            [--quick] [--paged] [--seed N]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.core import CommAdvisor, price
from repro.models.factory import make_model
from repro.serve import (ContinuousEngine, PagedContinuousEngine, ServeEngine,
                         ServeStats, poisson_workload, run_workload)

BENCH_JSON = "BENCH_serve.json"
SLO_MS = 120_000.0      # generous emulated-CPU completion-latency SLO


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, max(time.perf_counter() - t0, 1e-9)


def run(quick: bool = False, arch: str = "qwen2.5-3b", paged: bool = False,
        seed: int = 0, json_path: str = BENCH_JSON):
    batch = 4 if quick else 8
    prompt_len = 8 if quick else 16
    new_tokens = 6 if quick else 16
    max_len = prompt_len + new_tokens
    block_size = 4 if quick else 8

    cfg = get_arch(arch).reduced()
    model = make_model(cfg, moe_impl="dense")
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab_size))

    def _engine(n_slots):
        if paged:
            return PagedContinuousEngine(
                model=model, params=params, n_slots=n_slots, max_len=max_len,
                block_size=block_size)
        return ContinuousEngine(model=model, params=params, n_slots=n_slots,
                                max_len=max_len,
                                prefill_buckets=(prompt_len,))

    engine_name = "paged" if paged else "continuous"

    # ---- static engine ------------------------------------------------------
    static = ServeEngine(model=model, params=params, max_len=max_len)
    static.generate(prompts, 2)                      # warmup: jit compile
    out, dt = _timed(lambda: static.generate(prompts, new_tokens))
    static_tok_s = batch * new_tokens / dt
    print(f"static,batch={batch},new={new_tokens},wall_s={dt:.3f},"
          f"tok_s={static_tok_s:.1f}")

    # ---- continuous/paged engine, same all-at-t0 workload -------------------
    cont = _engine(batch)
    cont.run([(prompts[0], 2)])                      # warmup
    cont.stats = ServeStats(n_slots=batch)
    outs, dt_c = _timed(lambda: cont.run(
        [(prompts[i], new_tokens) for i in range(batch)]))
    n_tok = sum(len(o) for o in outs)
    parity = bool(np.array_equal(np.stack(outs), np.asarray(out)))
    cont_tok_s = n_tok / dt_c
    print(f"{engine_name},batch={batch},wall_s={dt_c:.3f},"
          f"tok_s={cont_tok_s:.1f},occupancy={cont.stats.occupancy:.3f},"
          f"greedy_parity={parity}")
    assert parity, f"{engine_name} engine drifted from static greedy outputs"

    # ---- staggered arrivals: more requests than slots -----------------------
    slots = max(2, batch // 2)
    stag = _engine(slots)
    stag.run([(prompts[0], 2)])                      # warmup
    stag.stats = ServeStats(n_slots=slots)
    reqs = [(prompts[i % batch], new_tokens - (i % 3), 2 * i)
            for i in range(batch)]
    outs_s, dt_s = _timed(lambda: stag.run(reqs))
    n_tok_s = sum(len(o) for o in outs_s)
    print(f"staggered,slots={slots},requests={len(reqs)},"
          f"wall_s={dt_s:.3f},tok_s={n_tok_s / dt_s:.1f},"
          f"occupancy={stag.stats.occupancy:.3f}")

    # ---- seeded Poisson load generation: deployment SLO numbers -------------
    # The same staggered engine (compile already paid) absorbs a Poisson
    # arrival process with mixed lengths; the report is what a deployment
    # is judged by — p50/p99 completion latency, TTFT, sustained tok/s.
    wl = poisson_workload(
        n=2 * batch, rate=0.5, seed=seed, vocab_size=cfg.vocab_size,
        prompt_len=f"uniform:{max(2, prompt_len // 2)}:{prompt_len}",
        new_tokens=f"uniform:2:{new_tokens}", max_len=max_len)
    (_, report), dt_l = _timed(lambda: run_workload(stag, wl, slo_ms=SLO_MS))
    print(f"loadgen,n={len(wl)},seed={seed},"
          f"p50_ms={report.latency_p50_ms:.1f},"
          f"p99_ms={report.latency_p99_ms:.1f},"
          f"ttft_p50_ms={report.ttft_p50_ms:.1f},"
          f"sustained_tok_s={report.sustained_tok_s:.1f},"
          f"slo_attainment={report.slo_attainment:.2f}")

    # ---- price the deployment's collectives under a CXL latency grid -------
    # One polymorphic call: the engine's compiled steps (prefill + decode)
    # are synthesized into bundles and priced in one batched evaluation,
    # weighted by the engine's OBSERVED step mix across the runs above.
    adv = CommAdvisor()
    grid = adv.default_grid(3, 3) if quick else adv.default_grid(4, 4)
    priced = price(stag, grid, advisor=adv)
    dep_weights = stag.step_weights()
    dep_speed = priced.predicted_speedup(weights=dep_weights)
    best = priced.best_scenario(weights=dep_weights)
    print(f"advisor,steps={len(priced)},scenarios={len(grid)},"
          f"best={grid.labels()[best]},speedup={dep_speed[best]:.3f}")

    bench = {
        "benchmark": "serve_throughput",
        "quick": bool(quick),
        "paged": bool(paged),
        "arch": arch,
        "seed": int(seed),
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "block_size": block_size if paged else None,
        "static": {"wall_s": dt, "tok_s": static_tok_s},
        "continuous": {"engine": engine_name, "wall_s": dt_c,
                       "tok_s": cont_tok_s, "greedy_parity": parity,
                       "occupancy": cont.stats.occupancy,
                       **dataclasses.asdict(cont.stats)},
        "staggered": {"wall_s": dt_s, "tok_s": n_tok_s / dt_s,
                      "occupancy": stag.stats.occupancy,
                      **dataclasses.asdict(stag.stats)},
        "loadgen": {"workload": wl.meta, "wall_s": dt_l, "slo_ms": SLO_MS,
                    **report.as_dict()},
        "advisor": {"steps": list(priced.names),
                    "step_weights": dep_weights,
                    "scenarios": len(grid),
                    "best_scenario": grid.labels()[best],
                    "best_deployment_speedup": float(dep_speed[best])},
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(bench, f, indent=2)
        print(f"wrote {json_path}")
    return bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="run the block/paged-KV engine in the continuous "
                         "sections (parity still asserted)")
    ap.add_argument("--seed", type=int, default=0,
                    help="arrival-process seed for the load generator")
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--json", default=BENCH_JSON,
                    help="output path for the machine-readable benchmark "
                         "record ('' disables)")
    args = ap.parse_args(argv)
    run(quick=args.quick, arch=args.arch, paged=args.paged, seed=args.seed,
        json_path=args.json)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
