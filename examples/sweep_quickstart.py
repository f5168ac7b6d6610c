"""Quickstart: the vectorized scenario-sweep engine behind ``price()``.

The per-call predictor answers "is message-free worth it?" for ONE
calibrated scenario.  The pricing front door answers it for a whole
design space at once: compile the trace bundle a single time, then
``price(cb, scenarios, plan=ExecPlan(...))`` — any ``ScenarioSet``
(factorial grid, Latin-hypercube sample, zipped design points, or a
concatenation of all three) through any registered backend.

1. Collect the stencil trace bundle (one measurement run, as always).
2. Compile it to packed arrays with ``compile_bundle``.
3. Price a (cxl_lat_ns x cxl_atomic_lat_ns) grid and read the
   ``(n_scenarios, n_calls)`` gain matrix + per-scenario aggregates.
4. Swap the MPI-side transfer model for LogGP (Sec. VI) without touching
   the access physics — or mix BOTH models inside one grid with the
   categorical ``mpi_transfer=`` axis.
5. Go beyond the factorial grid: ``ParamGrid.sample`` (Latin-hypercube
   exploration), ``ParamGrid.zip`` (paired calibration points) and
   ``ParamGrid.concat`` (union of all of them) price exactly the same way.
6. Re-run on the ``jax`` backend (jit-compiled, vmap-able), the
   ``pallas`` backend (the fused bracket/segment-sum kernel of
   ``kernels/sweep_bracket``, interpret mode on CPU), and chunked
   (bounded peak memory, bit-identical) — all via ``ExecPlan``.
7. Stream a 4k-scenario adaptive sweep through the ``distributed``
   backend (sharded top-k + exact aggregates, frontier refinement).
8. Audit your own jitted function with the IR-tier checker
   (``repro.analysis.ircheck``): register an entry spec, run the
   liveness / promotion / callback / donation / collective passes.

JAX-compat policy note: drift-prone JAX symbols (``shard_map``,
``axis_size``, ``segment_sum``, ``enable_x64``, ``cost_analysis``
normalization) are imported exclusively via ``repro.compat`` — add new
shims there, never version-branch at call sites.

Run:  PYTHONPATH=src python examples/sweep_quickstart.py
"""
import numpy as np

from repro.apps.stencil.spec import HALO_CALLS, StencilConfig, build_spec
from repro.core import (ExecPlan, LogGPTransfer, ModelParams, ParamGrid,
                        TRANSFER_MODELS, adaptive_sample, compile_bundle,
                        price)
from repro.memsim import collect
from repro.memsim.machine import NetworkParams


def main():
    # ---- 1+2: one measurement run, one compile ---------------------------
    cfg = StencilConfig(tile=32, grid=(8, 8), ranks_per_socket=6)
    bundle = collect(build_spec(cfg), network=NetworkParams.multinode(),
                     bw_share=cfg.bw_share,
                     ranks_per_socket=cfg.ranks_per_socket)
    cb = compile_bundle(bundle)
    print(f"compiled {cb.n_calls} call-sites, "
          f"{len(cb.hit_lat) + len(cb.lfb_lat) + len(cb.miss_lat)} samples")

    # ---- 3: 8x8 latency grid in one pass ---------------------------------
    grid = ParamGrid.product(
        ModelParams.multinode(),
        cxl_lat_ns=[float(v) for v in np.linspace(250.0, 700.0, 8)],
        cxl_atomic_lat_ns=[float(v) for v in np.linspace(300.0, 800.0, 8)])
    res = price(cb, grid)
    print(f"gain matrix shape: {res.gain_ns.shape}  (scenarios x calls)")

    speed = res.predicted_speedup(replaced=set(HALO_CALLS))
    best = res.best_scenario(replaced=set(HALO_CALLS))
    print(f"best scenario: {grid.labels()[best]} "
          f"-> {speed[best]:.3f}x app speedup")
    worst = int(np.argmin(speed))
    print(f"worst scenario: {grid.labels()[worst]} -> {speed[worst]:.3f}x")
    print(f"message-free wins every call in "
          f"{int((res.n_beneficial() == cb.n_calls).sum())}/{len(grid)} scenarios")

    # per-scenario capacity planning, still vectorized
    chosen, used = res.prioritize_for_capacity(capacity_bytes=64 * 1024)
    print(f"64 KiB CXL budget fits {chosen.sum(axis=1).min()}.."
          f"{chosen.sum(axis=1).max()} buffers depending on scenario")

    # ---- 4: LogGP transfer variant ---------------------------------------
    loggp = LogGPTransfer(L_ns=1200.0, o_ns=200.0, G_ns_per_byte=1 / 24.715)
    res_lg = price(cb, grid, mpi_transfer=loggp)
    s_lg = res_lg.predicted_speedup(replaced=set(HALO_CALLS))
    print(f"LogGP MPI baseline shifts the band to "
          f"[{s_lg.min():.3f}, {s_lg.max():.3f}]x")

    # ...or mix transfer models WITHIN one grid (a categorical axis).  The
    # built-in "loggp" entry is Hockney-calibrated (near-identical numbers
    # by design), so register the overhead-calibrated instance above under
    # its own name — TRANSFER_MODELS is an open registry:
    TRANSFER_MODELS["loggp_overhead"] = lambda p: loggp
    mixed = ParamGrid.product(
        ModelParams.multinode(),
        cxl_lat_ns=[300.0, 350.0, 400.0],
        mpi_transfer=["hockney", "loggp_overhead"])
    res_mix = price(cb, mixed)
    for row in res_mix.summary_rows(replaced=set(HALO_CALLS))[:2]:
        print(f"mixed-grid scenario {row['mpi_transfer']:14s} "
              f"@ {row['cxl_lat_ns']:.0f} ns "
              f"-> {row['predicted_speedup']:.3f}x")

    # ---- 5: beyond the factorial grid ------------------------------------
    # Latin-hypercube sample: 32 scattered design points over the same
    # band the 8x8 grid covers with 64 — plus the transfer model cycled in.
    sampled = ParamGrid.sample(ModelParams.multinode(), 32, seed=0,
                               cxl_lat_ns=(250.0, 700.0),
                               cxl_atomic_lat_ns=(300.0, 800.0),
                               mpi_transfer=["hockney", "loggp_overhead"])
    s_sam = price(cb, sampled).predicted_speedup(replaced=set(HALO_CALLS))
    print(f"LHS sample (32 pts) speedup band: "
          f"[{s_sam.min():.3f}, {s_sam.max():.3f}]x")
    # zip: the paper's two calibrated (lat, atomic) points move TOGETHER
    paper_pts = ParamGrid.zip(ModelParams.multinode(),
                              cxl_lat_ns=[350.0, 300.0],
                              cxl_atomic_lat_ns=[430.0, 350.0])
    s_pts = price(cb, paper_pts).predicted_speedup(replaced=set(HALO_CALLS))
    print(f"paper points (default, optimistic): "
          f"{s_pts[0]:.3f}x, {s_pts[1]:.3f}x")
    # concat: one union set — grid + sample + calibrated pairs in one pass
    union = ParamGrid.concat(grid, sampled, paper_pts)
    res_u = price(cb, union)
    print(f"union set: {len(union)} scenarios in one evaluation; "
          f"best {res_u.predicted_speedup(replaced=set(HALO_CALLS)).max():.3f}x")

    # ---- 6: same physics, other executors (ExecPlan) ---------------------
    def drift(other):          # max relative error vs the numpy matrices
        return np.max(np.abs(other.gain_ns - res.gain_ns)
                      / np.maximum(np.abs(res.gain_ns), 1e-12))

    res_jax = price(cb, grid, plan=ExecPlan("jax"))   # jit'd, accelerator-ready
    print(f"jax backend max relative drift vs numpy: {drift(res_jax):.2e}")
    # fused Pallas bracket/segment-sum kernel (interpreted on CPU; on a
    # TPU the same plan compiles it and prices in float32)
    res_pl = price(cb, grid, plan=ExecPlan("pallas"))
    print(f"pallas backend max relative drift vs numpy: {drift(res_pl):.2e}")
    res_chunk = price(cb, grid, plan=ExecPlan(chunk_scenarios=16))
    print(f"chunked numpy bit-identical: "
          f"{np.array_equal(res_chunk.gain_ns, res.gain_ns)}")

    # ---- 7: streaming distributed sweep + adaptive refinement ------------
    # The "distributed" backend shards the scenario axis over the device
    # mesh (shard_map) and streams: each chunk shard keeps only its local
    # top-k plus exact aggregates — the full (S, n_sites) matrices never
    # exist.  adaptive_sample builds a column-array ArraySet (same LHS
    # stream as ParamGrid.sample), and refine= rounds re-sample around the
    # running speedup frontier.  Scale the device count with
    # XLA_FLAGS=--xla_force_host_platform_device_count=N (or real devices).
    big = adaptive_sample(ModelParams.multinode(), 4096, seed=0,
                          cxl_lat_ns=(250.0, 700.0),
                          cxl_atomic_lat_ns=(300.0, 800.0),
                          mpi_transfer=["hockney", "loggp_overhead"])
    top = price(cb, big, plan="distributed:topk=8,refine=2")
    print(f"streamed {top.aggregates.count} scenario evaluations "
          f"({len(big)} seed + {top.plan.refine} refinement rounds); "
          f"per-shard working set {top.shard_rows} rows")
    print(f"top-{len(top)} speedups: "
          f"[{top.speedups[-1]:.4f}, {top.speedups[0]:.4f}]x; "
          f"best scenario {top.labels()[0]}")
    print(f"speedup histogram mass around 1.0x: "
          f"{int(top.aggregates.hist[19:23].sum())} scenarios")

    # ---- 8: audit your own entry point with the IR-tier checker ----------
    # Register a representative traced configuration of any jitted
    # function and ircheck runs its six passes over the jaxpr + compiled
    # HLO: peak-live-bytes liveness, silent f64 promotion, host
    # callbacks, donation effectiveness (input_output_alias), collective
    # vs mesh cross-check, and layout churn.  The repo's own sweep /
    # serve / train entry points register exactly this way — see
    # `python -m repro.analysis.ircheck --list`.
    import jax
    import jax.numpy as jnp
    from repro.analysis import ircheck

    def my_step(state, grad):                 # a toy "optimizer step"
        return state - 0.1 * grad, jnp.sum(jnp.abs(grad))

    abstract = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    spec = ircheck.EntrySpec(
        "quickstart.my_step", my_step, args=(abstract, abstract),
        donate_argnums=(0,))                  # state is donated in place
    report = ircheck.check_entry(spec)        # traced + lowered, never run
    print(f"ircheck {report.name}: {report.status}, "
          f"peak live {report.metrics['peak_live_bytes']:,} B, "
          f"layout churn {report.metrics['copy_transpose_bytes']:,} B")
    for f in report.findings:                 # e.g. a dead donation would
        print(f"  {f}")                       # land here as file:line rule
    # register_entrypoint("quickstart.my_step", lambda: spec) would make
    # `python -m repro.analysis.ircheck --entry quickstart.my_step` (and
    # the committed-baseline budget diff) pick it up too.


if __name__ == "__main__":
    main()
