"""CommAdvisor — the paper's per-call model applied to compiled JAX steps.

The paper scores each *MPI receive call-site*: Hockney transfer + post-
receive buffer loads (message-based) vs a 2-atomic handshake + direct
remote loads (message-free).  On TPU the call-sites are the HLO collectives
of the compiled step (DESIGN.md §2):

  message-based := the XLA collective as compiled — ring transfer over ICI,
                   then the consumer streams the result from LOCAL HBM.
  message-free  := semaphore-handshake remote DMA / pooled-HBM window
                   (kernels/halo_exchange) — no bulk transfer; the consumer
                   streams the operand from REMOTE memory at CXL-class
                   latency.

Mapping choices (documented per DESIGN.md §2):
  * transfer bytes  = ring wire bytes of the collective (receive direction);
  * the consumer's loads are synthesized as first-touch streaming samples at
    vector-unit granularity (no PEBS on TPU — the access stream of a
    compiled collective operand is statically known: touched exactly once);
  * whole-program characterization comes from the roofline terms of the
    same compiled artifact (the PAPI-counters role).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..compat import normalize_cost_analysis
from .execplan import _UNSET, ExecPlan, legacy_plan
from .hlo import (CollectiveOp, RooflineTerms, parse_collectives,
                  loop_corrected_cost)
from .params import ModelParams, TpuSpec, TPU_V5E
from .predictor import CallPrediction, RunPrediction, predict_run
from .pricing import price
from .sweep import MultiSweepResult, ParamGrid, SweepResult
from .traces import CallSite, CommRecord, CounterSet, DataSource, LoadSample, TraceBundle


def _remote_read_bytes(op: CollectiveOp) -> float:
    """Bytes the consumer must load from remote memory in the message-free
    formulation (one execution)."""
    if op.kind == "all-reduce":
        return op.wire_bytes / 2.0          # read remote partials once
    return op.wire_bytes


def synthesize_bundle(text: str, cost: dict, params: ModelParams,
                      spec: TpuSpec = TPU_V5E,
                      min_group: int = 2) -> TraceBundle:
    """Build the model's input bundle from a compiled step's HLO."""
    flops, hbm_bytes = loop_corrected_cost(cost, text)
    colls = parse_collectives(text)
    wire = sum(op.total_wire_bytes for op in colls)
    terms = RooflineTerms(flops=flops, hbm_bytes=hbm_bytes, wire_bytes=wire,
                          spec=spec)
    wall_ns = max(terms.step_time_s, 1e-12) * 1e9

    granule = params.avg_load_bytes
    bundle = TraceBundle(sampling_period=1.0,
                         meta={"flops": flops, "hbm_bytes": hbm_bytes,
                               "wire_bytes": wire, "wall_ns": wall_ns})
    # PAPI-analog counters: a statically-scheduled TPU step streams its HBM
    # traffic; vector loads all reach the backing memory.
    n_loads = hbm_bytes / granule
    bundle.counters = CounterSet(
        ld_ins=n_loads, l1_ldm=n_loads, l3_ldm=n_loads,
        tot_cyc=wall_ns * params.cpu_freq_ghz,
        imc_reads=hbm_bytes / 64.0,
        wall_time_ns=wall_ns)

    for i, op in enumerate(colls):
        if op.group_size < min_group:
            continue
        cid = f"{op.kind}@{op.computation}#{i}"
        site = bundle.call(cid)
        site.accesses_per_element = 1.0      # collective operands stream once
        site.loads_per_line = 1.0            # vector granule ~ cache line
        site.comms.append(CommRecord(
            call_id=cid, bytes=int(op.wire_bytes),
            count=max(1, int(round(op.multiplier)))))
        n_granules = _remote_read_bytes(op) * op.multiplier / granule
        if n_granules > 0:
            site.samples.append(LoadSample(
                call_id=cid, lat_ns=params.mem_lat_ns,
                source=DataSource.DRAM, weight=n_granules))
        site.meta = {"kind": op.kind, "group": op.group_size,
                     "multiplier": op.multiplier,
                     "result_bytes": op.result_bytes}
    return bundle


@dataclass
class AdvisorReport:
    run: RunPrediction
    terms: RooflineTerms
    collectives: list = field(default_factory=list)

    def summary_rows(self) -> list:
        rows = []
        for cid, c in sorted(self.run.calls.items(),
                             key=lambda kv: -kv[1].gain_ns):
            rows.append({
                "call": cid,
                "t_message_us": c.t_mpi_ns / 1e3,
                "t_free_us": c.t_cxl_ns / 1e3,
                "gain_us": c.gain_ns / 1e3,
                "speedup": c.speedup,
                "verdict": "message-free" if c.gain_ns > 0 else "message-based",
            })
        return rows

    @property
    def step_gain_us(self) -> float:
        return sum(max(0.0, c.gain_ns) for c in self.run.calls.values()) / 1e3


class CommAdvisor:
    """Scores every collective of a compiled step (the paper's questions
    1-3 at per-HLO-collective granularity)."""

    def __init__(self, params: ModelParams | None = None,
                 spec: TpuSpec = TPU_V5E):
        self.params = params or ModelParams.tpu_v5e_ici()
        self.spec = spec

    def analyze_text(self, text: str, cost: dict | None = None) -> AdvisorReport:
        cost = cost or {}
        bundle = synthesize_bundle(text, cost, self.params, self.spec)
        flops = bundle.meta["flops"]
        run = predict_run(bundle, self.params)
        terms = RooflineTerms(flops=flops, hbm_bytes=bundle.meta["hbm_bytes"],
                              wire_bytes=bundle.meta["wire_bytes"],
                              spec=self.spec)
        run.baseline_runtime_ns = bundle.meta["wall_ns"]
        return AdvisorReport(run=run, terms=terms,
                             collectives=parse_collectives(text))

    def analyze_compiled(self, compiled) -> AdvisorReport:
        return self.analyze_text(compiled.as_text(),
                                 normalize_cost_analysis(compiled))

    # ------------------------------------------------------------- sweeps
    def default_grid(self, n_lat: int = 8, n_atomic: int = 8) -> ParamGrid:
        """Latency-band grid around this advisor's params: remote-access
        latency x handshake latency at 0.5x..3x — the 2-3x band the CXL
        pooling evaluations report."""
        p = self.params
        return ParamGrid.product(
            p,
            cxl_lat_ns=[float(v) for v in
                        np.linspace(0.5 * p.cxl_lat_ns, 3.0 * p.cxl_lat_ns,
                                    n_lat)],
            cxl_atomic_lat_ns=[float(v) for v in
                               np.linspace(0.5 * p.cxl_atomic_lat_ns,
                                           3.0 * p.cxl_atomic_lat_ns,
                                           n_atomic)])

    def _grid(self, grid):
        return grid if grid is not None else self.default_grid()

    def sweep_text(self, text: str, grid: ParamGrid | None = None,
                   cost: dict | None = None, backend=_UNSET,
                   chunk_scenarios=_UNSET, pallas_interpret=_UNSET,
                   plan: ExecPlan | None = None) -> SweepResult:
        """Score every collective under a whole scenario grid in one pass —
        a thin shim over :func:`repro.core.price` (synthesize the bundle
        with THIS advisor's params, then price it under ``plan``).  The
        ``backend=`` / ``chunk_scenarios=`` / ``pallas_interpret=`` kwargs
        are DEPRECATED in favour of ``plan=ExecPlan(...)``."""
        plan = legacy_plan(plan, "CommAdvisor.sweep_text", backend=backend,
                           chunk_scenarios=chunk_scenarios,
                           pallas_interpret=pallas_interpret)
        bundle = synthesize_bundle(text, cost or {}, self.params, self.spec)
        return price(bundle, self._grid(grid), plan=plan)

    def sweep(self, compiled, grid: ParamGrid | None = None, backend=_UNSET,
              chunk_scenarios=_UNSET, pallas_interpret=_UNSET,
              plan: ExecPlan | None = None) -> SweepResult:
        """``price(compiled, grid)`` with this advisor's params (the
        batched analog of ``analyze_compiled``); the legacy execution
        kwargs are DEPRECATED shims."""
        plan = legacy_plan(plan, "CommAdvisor.sweep", backend=backend,
                           chunk_scenarios=chunk_scenarios,
                           pallas_interpret=pallas_interpret)
        return price(compiled, self._grid(grid), plan=plan, advisor=self)

    # ------------------------------------------------- multi-step sweeps
    def sweep_text_many(self, texts, grid: ParamGrid | None = None,
                        costs=None, names=None, backend=_UNSET,
                        chunk_scenarios=_UNSET, pallas_interpret=_UNSET,
                        plan: ExecPlan | None = None) -> MultiSweepResult:
        """Score the collectives of MANY HLO programs under one grid in a
        single batched evaluation (the multi-bundle ``price`` core): every
        step's bundle is packed into one offset-segment-id super-bundle,
        so the pricing kernel runs once for all steps x scenarios.

        ``texts`` may be a ``{name: hlo_text}`` dict (names label the
        per-step results; an explicit ``names`` selects/reorders entries)
        or a plain sequence; ``costs`` aligns with it — a sequence matches
        ``texts`` positionally, a dict is keyed by step name (``None``
        entries mean no cost analysis for that step).  Legacy execution
        kwargs are DEPRECATED shims over ``plan=``."""
        plan = legacy_plan(plan, "CommAdvisor.sweep_text_many",
                           backend=backend, chunk_scenarios=chunk_scenarios,
                           pallas_interpret=pallas_interpret)
        if isinstance(texts, dict):
            if names is None:
                names = tuple(texts)
            texts = [texts[n] for n in names]
        else:
            texts = list(texts)
        if costs is None:
            costs = [None] * len(texts)
        elif isinstance(costs, dict):
            if names is None:
                raise ValueError("costs given as a dict need named steps "
                                 "(a texts dict or an explicit names=)")
            costs = [costs.get(n) for n in names]
        bundles = [synthesize_bundle(t, c or {}, self.params, self.spec)
                   for t, c in zip(texts, costs)]
        return price(bundles, self._grid(grid), plan=plan, names=names)

    def sweep_many(self, compiled_steps, grid: ParamGrid | None = None,
                   names=None, backend=_UNSET, chunk_scenarios=_UNSET,
                   pallas_interpret=_UNSET,
                   plan: ExecPlan | None = None) -> MultiSweepResult:
        """``price(compiled_steps, grid)`` with this advisor's params —
        the whole-deployment analog of :meth:`sweep`.  ``compiled_steps``
        is a ``{name: compiled}`` dict (e.g. a serving engine's prefill
        buckets + decode step) or a sequence of compiled artifacts; legacy
        execution kwargs are DEPRECATED shims."""
        plan = legacy_plan(plan, "CommAdvisor.sweep_many", backend=backend,
                           chunk_scenarios=chunk_scenarios,
                           pallas_interpret=pallas_interpret)
        return price(compiled_steps, self._grid(grid), plan=plan,
                     names=names, advisor=self)

    def sweep_serve(self, engine, grid: ParamGrid | None = None,
                    backend=_UNSET, chunk_scenarios=_UNSET,
                    pallas_interpret=_UNSET, plan: ExecPlan | None = None,
                    **compile_kwargs) -> MultiSweepResult:
        """Price a serving deployment's collectives under the grid in one
        batched call: the engine's steps (prefill buckets + decode) are
        compiled once via ``engine.compiled_steps()`` and priced together.
        Works with both ``serve.ServeEngine`` and the continuous
        ``serve.PagedContinuousEngine`` — and is itself a shim over
        ``price(engine, grid)``; legacy execution kwargs are DEPRECATED."""
        plan = legacy_plan(plan, "CommAdvisor.sweep_serve", backend=backend,
                           chunk_scenarios=chunk_scenarios,
                           pallas_interpret=pallas_interpret)
        return price(engine.compiled_steps(**compile_kwargs),
                     self._grid(grid), plan=plan, advisor=self)
