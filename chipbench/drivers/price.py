"""Driver for the pricing engine: repeated ``price()`` sweeps of a bundle set.

Set-up records the configuration's trace bundles (the paper's mini-apps
through the memory simulator), draws ``sets`` seeded scenario designs of
one size and prices one sweep to compile and warm the plan.  The window
prices the designs in turn, one ``price(bundles, design, plan)`` call per
sweep, and takes each bundle's per-scenario speedup on the host.  The
check re-prices a seeded sample of the rows of a seeded choice of the
window's sweeps with the float64 reference in ``reference/pricing.py``.
"""
from __future__ import annotations

import math

import numpy as np

from chipbench import generate
from chipbench.harness import Check
from chipbench.reference import pricing as ref

FIELDS = ("t_transfer_mpi_ns", "t_transfer_cxl_ns", "t_access_mpi_ns",
          "t_access_cxl_ns")
TRANSFER = ("hockney", "loggp")


def record_bundles(cfg: dict) -> list:
    """The configuration's trace bundles, as the mini-apps' validation
    runs record them (fixed recording seed: the bundles are the
    configuration, not the traffic)."""
    from repro.memsim.hooks import collect
    out = []
    for group in cfg["bundles"]:
        if group["app"] == "stencil":
            from repro.apps.stencil.spec import StencilConfig as C, build_spec
            from repro.apps.stencil.validation import NETWORK
            apps = [C(tile=s) for s in group["sizes"]]
        else:
            from repro.apps.hpcg.spec import HpcgConfig as C, build_spec
            from repro.apps.hpcg.validation import NETWORK
            apps = [C(nx=s) for s in group["sizes"]]
        out += [collect(build_spec(a), network=NETWORK, seed=0,
                        bw_share=a.bw_share,
                        ranks_per_socket=a.ranks_per_socket) for a in apps]
    return out


def machine_params(m: dict):
    from repro.core import ModelParams
    from repro.core.params import Thresholds
    scal = {k: v for k, v in m.items() if k != "thresholds"}
    thr = {f"thr_{k}": Thresholds(*v) for k, v in m["thresholds"].items()}
    return ModelParams(**scal, **thr)


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.cell.config
        self.tr = ctx.cell.traffic
        self.attempted = self.failed = 0
        self.kept = []                  # (sweep index, design index, result)
        self.pick = generate.rng_for(ctx.seed, 6)

    # ------------------------------------------------------------ set-up
    def setup(self):
        from repro.core import ExecPlan, compile_bundle
        from repro.core.adaptive import ArraySet
        self.bundles = record_bundles(self.cfg)
        self.cbs = [compile_bundle(b) for b in self.bundles]
        self.ctx.mark("bundles")
        base = machine_params(self.cfg["machine"])
        n = int(self.tr["scenarios"])
        self.designs = []
        for k in range(int(self.tr["sets"])):
            cols, codes = generate.lhs(
                n, self.tr["ranges"], {"mpi_transfer": TRANSFER},
                self.ctx.seed * 16 + k)
            self.designs.append(ArraySet(
                base=base, n=n, columns=cols,
                cat={"mpi_transfer": (codes["mpi_transfer"], TRANSFER)},
                ranges={**{a: tuple(v) for a, v in self.tr["ranges"].items()},
                        "mpi_transfer": TRANSFER}))
        self.plan = ExecPlan.parse(self.tr["plan"])
        self.ctx.mark("designs")
        self._sweep(self.designs[0])    # every design has the same shapes
        self.ctx.mark("warm sweep")
        c = self.ctx.counters
        c["scenarios_per_sweep"] = n
        c["n_calls"] = sum(cb.n_calls for cb in self.cbs)
        for g in ("hit", "lfb", "miss"):
            c[f"n_{g}"] = sum(len(getattr(cb, g + "_lat")) for cb in self.cbs)

    def _sweep(self, design):
        from repro.core import price
        res = price(self.cbs, design, plan=self.plan)
        speedups = [r.predicted_speedup() for r in res]
        return res, speedups

    # ------------------------------------------------------------ window
    def window(self, win):
        import time

        import jax
        i = 0
        self.sweep_s = []
        while win.running():
            k = i % len(self.designs)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("cb.sweep"):
                try:
                    res, sp = self._sweep(self.designs[k])
                except Exception as e:          # a failed sweep counts
                    self.ctx.say(f"sweep {i} failed: {e!r}")
                    res, sp = None, None
            self.sweep_s.append(time.perf_counter() - t0)
            self.attempted += 1
            if res is None or not all(np.isfinite(s).all() for s in sp):
                self.failed += 1
            else:
                self._keep(i, k, res)
            i += 1

    def _keep(self, i, k, res):
        """Reservoir of ``check_sweeps`` sweeps, drawn from the seed."""
        cap = int(self.tr["check_sweeps"])
        if len(self.kept) < cap:
            self.kept.append((i, k, res))
            return
        j = int(self.pick.integers(0, i + 1))
        if j < cap:
            self.kept[j] = (i, k, res)

    def drain(self):
        pass

    def end_to_end(self, window_s: float) -> dict:
        done = self.attempted - self.failed
        q = np.quantile(self.sweep_s, [0, 0.25, 0.5, 0.75, 1]) \
            if self.sweep_s else []
        self.ctx.say("sweep s (min q1 median q3 max): "
                     + " ".join(f"{v:.4f}" for v in q) + "; in order: "
                     + " ".join(f"{v:.3f}" for v in self.sweep_s))
        return {"scenarios_per_s":
                done * self.tr["scenarios"] / window_s}

    def release(self):
        self.cbs = None

    # ------------------------------------------------------------ check
    def compare(self, rnd=None, rows=None) -> dict:
        """Max relative error of the kept sweeps against the reference
        (``rnd`` prices the reference in a lower precision instead: the
        control).  Returns ``{"components": e, "speedup": e}``."""
        m = self.cfg["machine"]
        n_rows = int(self.tr["check_rows"])
        err = {"components": 0.0, "speedup": 0.0}
        for i, k, res in self.kept:
            d = self.designs[k]
            idx = np.sort(generate.rng_for(self.ctx.seed, 100 + i).choice(
                d.n, size=min(n_rows, d.n), replace=False)) \
                if rows is None else rows
            code = d.cat["mpi_transfer"][0][idx]
            for b, r in zip(self.bundles, res.results):
                want = ref.price_bundle(
                    b, d.columns["cxl_lat_ns"][idx],
                    d.columns["cxl_atomic_lat_ns"][idx], code, m, rnd=rnd)
                for f in FIELDS:
                    err["components"] = max(err["components"], max_rel(
                        getattr(r, f)[idx], want[f]))
                err["speedup"] = max(err["speedup"], max_rel(
                    r.predicted_speedup()[idx], want["speedup"]))
        return err

    def check(self) -> list:
        lim = self.tr["limits"]
        if not self.kept:
            return [Check("sweeps_checked", 0.0, -1.0)]
        err = self.compare()
        plan = self.kept[0][2].results[0].plan
        wrong_plan = float(plan.backend != "pallas"
                           or plan.pallas_interpret is not False)
        return [Check("price_rel_err", err["components"],
                      lim["price_rel_err"]),
                Check("speedup_rel_err", err["speedup"],
                      lim["speedup_rel_err"]),
                Check("plan_not_compiled_kernel", wrong_plan, 0.0)]


def max_rel(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return math.inf
    if got.size == 0:
        return 0.0
    e = np.abs(got - want) / np.maximum(np.abs(want), 1e-30)
    return float(np.max(np.where(np.isfinite(e), e, math.inf)))
