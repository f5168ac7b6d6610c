#!/usr/bin/env python3
"""Drive the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: serve, pricing, train
    python chip_smoke.py --chips 4   # four chips: the multi-chip paths only

One process, no child processes.  Every phase prints what ran, its sizes,
its compile seconds and its checks.  A failed check fails its phase; the
other phases still run, and the script then exits nonzero.  Without a TPU (or without the repository's ``src/`` next
to this file) it exits nonzero before any phase runs.  The last line of a
successful run is one JSON object naming the device::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

One-chip phases:
  * serve — qwen2.5-3b at its published size (36 layers, bf16, random
    weights from ``--seed``) through ``PagedContinuousEngine``, the engine
    of ``python -m repro.launch.serve --arch qwen2.5-3b --paged``; its
    first two greedy tokens per request are checked against a float32
    reference forward of the same weights.
  * pricing — the paper's stencil and HPCG bundles at every published
    size, priced with ``price()`` over 65,536 Latin-hypercube scenarios
    by the ``jax`` backend in float64 and float32 and by the compiled
    Pallas kernel, each against the ``numpy`` float64 reference.
  * train — five steps of ``launch.train.train`` on a (1, 1) mesh,
    qwen2.5-3b at published widths with the depth cut to fit one chip.

Four-chip phases (``--chips 4``):
  * the ``distributed`` sweep backend over 4 devices against ``numpy``
    (top-k survivors and exact aggregates);
  * the 2-D stencil on a 2x2 mesh, message-based and message-free,
    against the single-device ``reference_step``;
  * the Pallas remote-DMA halo ring against the ``ppermute`` oracle.

The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``.jax_cache/`` in this checkout.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# --- serve phase -----------------------------------------------------------
SERVE_ARCH = "qwen2.5-3b"
SERVE_REQUESTS = 8
SERVE_PROMPT = (128, 512)        # prompt lengths drawn uniformly, inclusive
SERVE_NEW = 32
SERVE_BLOCK = 16
#: a greedy token passes when its float32-reference logit is within this
#: many standard deviations (of that reference logit row) of the row's
#: maximum: bf16 rounding through 36 layers moves logits by a few hundredths
#: of a deviation, while a wrong token sits several deviations below
SERVE_TOL_STD = 0.25

# --- pricing phase ---------------------------------------------------------
STENCIL_TILES = (32, 128, 512, 1024, 2048, 4096, 8096)    # paper Fig. 5/7
HPCG_SIZES = (16, 32, 64, 104, 128, 192, 256)             # paper Fig. 9/10
N_SCENARIOS = 65_536
PRICE_CHUNK = 16_384             # scenario chunk of the matrix backends
#: max relative error against numpy float64: CI's bound for jax at x64,
#: 1e-5 for the float32 paths
PRICE_BOUNDS = (("jax", 1e-6), ("jax:x64=0", 1e-5), ("pallas", 1e-5))

# --- train phase -----------------------------------------------------------
#: depth cut for one 16 GB chip.  A described-topology (v5e) compile of
#: this train step reports 11.3 GB (bf16 params + float32 AdamW moments +
#: grads + activations); 6 layers would be 13.5 GB, the published 36 do
#: not fit.
TRAIN_LAYERS = 4
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 512, 5

# --- four-chip phases ------------------------------------------------------
DIST_SCENARIOS = 4 * 65_536
STENCIL_PLANE, STENCIL_STEPS = 4096, 10
HALO_BLOCK = (64, 1024)          # per-device block of the halo ring


def say(*parts) -> None:
    print(*parts, flush=True)


def check(ok: bool, what: str) -> None:
    say(f"  check {what}: {'pass' if ok else 'FAIL'}")
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def max_rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.size == 0:
        return 0.0
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


def mem_line(jax) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    return (f"device memory: in use {stats.get('bytes_in_use', 0) / 1e9:.2f}"
            f" GB, peak {stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_serve(jax, seed: int) -> None:
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.models import factory
    from repro.models.reference import reference_logits
    from repro.serve.paged import PagedContinuousEngine, ServeStats

    cfg = get_arch(SERVE_ARCH)
    say(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}, {cfg.dtype}")
    model = factory.make_model(cfg)
    params, init_s = timed(lambda: jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(seed))))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    say(f"  params {n_params / 1e9:.3f} B "
        f"({sum(x.nbytes for x in jax.tree.leaves(params)) / 1e9:.2f} GB), "
        f"init (compile included) {init_s:.1f} s")

    rng = np.random.default_rng(seed)
    lens = rng.integers(SERVE_PROMPT[0], SERVE_PROMPT[1] + 1,
                        size=SERVE_REQUESTS)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in lens]
    max_len = SERVE_PROMPT[1] + SERVE_NEW
    engine = PagedContinuousEngine(
        model=model, params=params, n_slots=SERVE_REQUESTS, max_len=max_len,
        block_size=SERVE_BLOCK)
    say(f"  PagedContinuousEngine: {engine.n_slots} slots, max_len {max_len}"
        f", block_size {SERVE_BLOCK}, pool {engine.pool_blocks} blocks "
        f"({engine.block_bytes * engine.pool_blocks / 1e9:.3f} GB KV)")
    _, warm_s = timed(engine.run, [(prompts[0][:SERVE_BLOCK], 2)])
    say(f"  compile (chunk prefill + decode + sampler, one warm-up "
        f"request): {warm_s:.1f} s")
    engine.stats = ServeStats(n_slots=engine.n_slots)
    outs, run_s = timed(engine.run, [(p, SERVE_NEW) for p in prompts])
    st = engine.stats
    say(f"  served {len(outs)} requests, prompts {lens.min()}-{lens.max()} "
        f"tokens ({int(lens.sum())} in all), {SERVE_NEW} new tokens each, "
        f"in {run_s:.2f} s: {st.prefills_by_bucket} chunk steps, "
        f"{st.decode_steps} decode steps, kv peak "
        f"{engine.kv_bytes_peak / 1e6:.1f} MB")
    check(all(len(o) == SERVE_NEW for o in outs)
          and all(((o >= 0) & (o < cfg.vocab_size)).all() for o in outs),
          f"{SERVE_REQUESTS} requests x {SERVE_NEW} tokens in vocab range")

    # float32 reference: prompt + first generated token, right-padded to
    # one length (causal, so padding never reaches the queried positions)
    width = -(-(int(lens.max()) + 1) // 8) * 8
    toks = np.zeros((SERVE_REQUESTS, width), np.int32)
    at = np.zeros((SERVE_REQUESTS, 2), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        toks[i, :len(p)] = p
        toks[i, len(p)] = o[0]
        at[i] = (len(p) - 1, len(p))
    ref_fn = jax.jit(lambda pr, t, a: reference_logits(cfg, pr, t, a))
    ref, ref_s = timed(lambda: np.asarray(
        ref_fn(params, jnp.asarray(toks), jnp.asarray(at))))
    say(f"  float32 reference forward of {SERVE_REQUESTS} x {width} tokens "
        f"(compile included): {ref_s:.1f} s")
    margins, top1 = [], 0
    for i, o in enumerate(outs):
        for j in range(2):
            row = ref[i, j]
            margins.append((row.max() - row[o[j]]) / row.std())
            top1 += int(np.argmax(row) == o[j])
    say(f"  greedy tokens vs float32 reference: {top1}/{len(margins)} equal "
        f"the reference argmax; worst gap {max(margins):.4f} std "
        f"(tolerance {SERVE_TOL_STD} std)")
    check(np.isfinite(ref).all() and max(margins) <= SERVE_TOL_STD,
          "first two greedy tokens of every request match the float32 "
          "reference")
    say(f"  {mem_line(jax)}")


def _bundles(kind: str, sizes):
    from repro.core import compile_bundle
    from repro.memsim.hooks import collect
    if kind == "stencil":
        from repro.apps.stencil.spec import StencilConfig, build_spec
        from repro.apps.stencil.validation import NETWORK
        cfgs = [StencilConfig(tile=t) for t in sizes]
    else:
        from repro.apps.hpcg.spec import HpcgConfig, build_spec
        from repro.apps.hpcg.validation import NETWORK
        cfgs = [HpcgConfig(nx=n) for n in sizes]
    return [compile_bundle(collect(build_spec(c), network=NETWORK, seed=0,
                                   bw_share=c.bw_share,
                                   ranks_per_socket=c.ranks_per_socket))
            for c in cfgs]


def _scenarios(n: int, seed: int):
    from repro.core import ModelParams, adaptive_sample
    return adaptive_sample(ModelParams.multinode(), n, seed=seed,
                           cxl_lat_ns=(250.0, 700.0),
                           cxl_atomic_lat_ns=(300.0, 800.0),
                           mpi_transfer=["hockney", "loggp"])


def _multi_err(got, ref) -> float:
    """Max relative error over every bundle's four component matrices and
    its per-scenario speedup.  The gain (MPI minus CXL time) is left out:
    it is a difference, so its relative error is unbounded at break-even."""
    from repro.core.sweep_kernel import MATRIX_FIELDS
    return max(max_rel(x, y)
               for g, r in zip(got, ref)
               for x, y in [(getattr(g, f), getattr(r, f))
                            for f in MATRIX_FIELDS]
               + [(g.predicted_speedup(), r.predicted_speedup())])


def phase_pricing(jax, seed: int) -> None:
    from repro.core import ExecPlan, price

    grid = _scenarios(N_SCENARIOS, seed)
    say(f"[pricing] {len(grid)} scenarios (LHS over cxl_lat_ns, "
        "cxl_atomic_lat_ns, mpi_transfer); error = max relative error of "
        "the four priced component matrices and the speedup vs numpy")
    for kind, sizes in (("stencil", STENCIL_TILES), ("hpcg", HPCG_SIZES)):
        cbs = _bundles(kind, sizes)
        n_samples = sum(len(cb.hit_w) + len(cb.lfb_w) + len(cb.miss_w)
                        for cb in cbs)
        say(f"  {kind} bundles at {sizes}: "
            f"{sum(cb.n_calls for cb in cbs)} call-sites, {n_samples} "
            "samples")
        ref, ref_s = timed(price, cbs, grid,
                           plan=ExecPlan(chunk_scenarios=PRICE_CHUNK))
        say(f"    numpy (float64 reference): {ref_s:.2f} s")
        for spec, bound in PRICE_BOUNDS:
            plan = ExecPlan.parse(spec)
            if plan.backend == "jax":
                plan = plan.replace(chunk_scenarios=PRICE_CHUNK)
            got, cold_s = timed(price, cbs, grid, plan=plan)
            got, warm_s = timed(price, cbs, grid, plan=plan)
            used = got.results[0].plan
            err = _multi_err(got, ref)
            say(f"    {spec:10s} plan {used.to_string()}: first call "
                f"{cold_s:.2f} s (compile), second {warm_s:.3f} s, "
                f"max rel err {err:.3e} (bound {bound:.0e})")
            if used.backend == "pallas":
                check(used.pallas_interpret is False and used.x64 is False,
                      f"{kind} pallas kernel compiled (not interpreted), "
                      "float32")
            check(err < bound, f"{kind} {spec} within {bound:.0e} of numpy")
    say(f"  {mem_line(jax)}")


def phase_train(jax, seed: int) -> None:
    from repro.configs import get_arch
    from repro.launch.mesh import make_mesh
    from repro.launch.train import train
    from repro.models.config import ShapeConfig

    full = get_arch(SERVE_ARCH)
    cfg = full.replace(n_layers=TRAIN_LAYERS)
    shape = ShapeConfig("chip-smoke", "train", TRAIN_SEQ, TRAIN_BATCH)
    say(f"[train] {cfg.name} at published widths, reduced: n_layers "
        f"{full.n_layers} -> {TRAIN_LAYERS} (one 16 GB chip); batch "
        f"{TRAIN_BATCH} x seq {TRAIN_SEQ}, {TRAIN_STEPS} steps, (1, 1) mesh")
    mesh = make_mesh((1, 1), ("data", "model"))
    (params, hist), total_s = timed(train, cfg, shape, mesh, TRAIN_STEPS,
                                    log_every=1, seed=seed)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    steps = [h["elapsed_s"] for h in hist]
    say(f"  params {n_params / 1e9:.3f} B; first step (compile included) "
        f"{steps[0]:.1f} s, {TRAIN_STEPS} steps {steps[-1]:.1f} s, "
        f"train() {total_s:.1f} s")
    say("  loss " + " ".join(f"{h['loss']:.4f}" for h in hist)
        + " | grad norm " + " ".join(f"{h['grad_norm']:.3f}" for h in hist))
    check(len(hist) == TRAIN_STEPS
          and all(np.isfinite([h["loss"], h["grad_norm"]]).all()
                  for h in hist),
          f"{TRAIN_STEPS} steps with finite loss and grad norm")
    say(f"  {mem_line(jax)}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_distributed(jax, seed: int) -> None:
    from repro.core import ExecPlan, SweepAggregates, price
    from repro.core.sweep_kernel import MATRIX_FIELDS

    grid = _scenarios(DIST_SCENARIOS, seed)
    k = 64
    plan = ExecPlan.parse(f"distributed:devices=4,topk={k}")
    say(f"[distributed] {len(grid)} scenarios sharded over 4 devices, "
        f"top-{k} + exact aggregates vs numpy")
    for kind, (cb,) in (("stencil tile 32", _bundles("stencil", (32,))),
                        ("hpcg nx 104", _bundles("hpcg", (104,)))):
        ref = price(cb, grid, plan=ExecPlan(chunk_scenarios=PRICE_CHUNK))
        got, cold_s = timed(price, cb, grid, plan=plan)
        got, warm_s = timed(price, cb, grid, plan=plan)
        sp, i = ref.predicted_speedup(), got.indices
        ragg, agg = SweepAggregates.from_result(ref), got.aggregates
        errs = {
            "survivors' speedups": max_rel(got.speedups, sp[i]),
            "survivors' components": max(
                max_rel(getattr(got.result, f), getattr(ref, f)[i])
                for f in MATRIX_FIELDS),
            "speedup mean/min/max": max_rel(
                [agg.speedup_mean, agg.speedup_min, agg.speedup_max],
                [ragg.speedup_mean, ragg.speedup_min, ragg.speedup_max]),
            "per-call gain sums": max_rel(agg.gain_sum, ragg.gain_sum)}
        say(f"  {kind}: first call {cold_s:.2f} s (compile), second "
            f"{warm_s:.3f} s, plan {got.plan.to_string()}, shard rows "
            f"{got.shard_rows}; max rel err: "
            + ", ".join(f"{k} {v:.3e}" for k, v in errs.items()))
        err = max(errs.values())
        check(set(got.indices.tolist()) == set(ref.topk(k).tolist()),
              f"{kind} top-{k} survivors equal numpy's")
        check(agg.count == ragg.count and np.array_equal(agg.hist, ragg.hist)
              and np.array_equal(agg.n_beneficial, ragg.n_beneficial),
              f"{kind} exact counts, histogram and per-call benefit counts")
        check(err < 1e-9, f"{kind} survivors and aggregates within 1e-9 "
              "of numpy")


def phase_stencil(jax, seed: int) -> None:
    from repro.apps.stencil.jax_impl import (init_plane, make_runner,
                                             reference_step)
    from repro.comm.topology import grid_mesh

    mesh = grid_mesh(2, 2)
    plane = init_plane(STENCIL_PLANE, STENCIL_PLANE)
    ref = plane
    for _ in range(STENCIL_STEPS):
        ref = reference_step(ref)
    ref = np.asarray(ref)
    say(f"[stencil] {STENCIL_PLANE}x{STENCIL_PLANE} float32 plane on a 2x2 "
        f"mesh, {STENCIL_STEPS} Jacobi steps vs the single-device "
        "reference_step")
    for backend in ("message_based", "message_free"):
        run = make_runner(mesh, backend)
        out, cold_s = timed(lambda: np.asarray(run(plane, STENCIL_STEPS)))
        _, warm_s = timed(lambda: np.asarray(run(plane, STENCIL_STEPS)))
        err = float(np.max(np.abs(out - ref)))
        say(f"  {backend}: first call {cold_s:.2f} s (compile), second "
            f"{warm_s:.3f} s, max |err| {err:.3e}")
        check(err <= 1e-6, f"{backend} within 1e-6 of reference_step")


def phase_halo(jax, seed: int) -> None:
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.compat import make_mesh, shard_map
    from repro.kernels.halo_exchange.ops import (exchange_planes_1d,
                                                 exchange_planes_1d_oracle)

    mesh = make_mesh((4,), ("ring",), devices=jax.devices()[:4])
    rows, width = HALO_BLOCK
    x = jax.random.normal(jax.random.PRNGKey(seed), (4 * rows, width),
                          jnp.float32)

    def ring(exchange):
        def body(block):
            lo, hi = exchange(block, "ring")
            return jnp.concatenate([lo, hi], axis=0)
        return jax.jit(shard_map(body, mesh=mesh, in_specs=P("ring"),
                                 out_specs=P("ring"), check_vma=False))

    say(f"[halo] Pallas remote-DMA ring over 4 devices, {rows}x{width} "
        "float32 block each, vs the ppermute oracle")
    got, cold_s = timed(lambda: np.asarray(ring(exchange_planes_1d)(x)))
    want = np.asarray(ring(exchange_planes_1d_oracle)(x))
    say(f"  ring kernel (compile included) {cold_s:.2f} s; max |diff| "
        f"{float(np.max(np.abs(got - want))):.3e}")
    check(np.array_equal(got, want), "every device received its ring "
          "neighbours' boundary planes, bit for bit")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths, on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"no repository next to this script: "
                         f"{ROOT / 'src' / 'repro'} is missing")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {dev.platform} "
                         f"({dev.device_kind})")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices, "
                         f"JAX sees {len(devices)}")
    say(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
        f"compilation cache {cache}")

    phases = ((phase_distributed, phase_stencil, phase_halo)
              if args.chips == 4 else
              (phase_serve, phase_pricing, phase_train))
    failed = []
    for phase in phases:
        name = phase.__name__[6:]
        t0 = time.perf_counter()
        try:
            phase(jax, args.seed)
            status = "done"
        except Exception:        # report it, run the other phases, exit 1
            traceback.print_exc()
            failed.append(name)
            status = "FAILED"
        gc.collect()             # drop the phase's device buffers
        say(f"  phase {name} {status} in {time.perf_counter() - t0:.1f} s")
    if failed:
        say(f"failed phases: {', '.join(failed)}")
        return 1

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
