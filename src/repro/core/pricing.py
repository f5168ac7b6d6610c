"""``price()`` — the one polymorphic front door of the pricing engine.

Everything the repo can price goes through this single call:

    price(bundle, grid)                          # TraceBundle
    price(cb, grid, plan=ExecPlan("pallas"))     # CompiledBundle
    price(hlo_text, grid)                        # HLO text (advisor path)
    price(step.compile(), grid)                  # one compiled jax artifact
    price({"prefill@32": c1, "decode": c2},      # dict of compiled steps
          grid, plan="jax")                      #   -> MultiSweepResult
    price([bundle_a, bundle_b], grid)            # sequence of bundles
    price(engine, grid)                          # serve engine (its
                                                 #   compiled_steps())

``scenarios`` is any :class:`~repro.core.sweep.ScenarioSet` —
``ParamGrid.product`` / ``sample`` / ``zip`` / ``concat`` or a plain
iterable of ``ModelParams`` — and ``plan`` is an
:class:`~repro.core.execplan.ExecPlan` (or its string form, parsed via
``ExecPlan.parse``).  Single subjects return a ``SweepResult``;
collections, mappings and engines return a ``MultiSweepResult`` keyed by
``names`` (mapping keys by default).

Subjects that are not already trace bundles are lowered through a
``CommAdvisor`` (``advisor=`` overrides the default one) —
``synthesize_bundle`` turns HLO text / compiled artifacts into the
model's input bundle exactly as the legacy ``CommAdvisor.sweep_*``
methods did; those methods are now thin shims over this function.
"""
from __future__ import annotations

from collections.abc import Mapping, Sequence

from .execplan import ExecPlan
from .params import ModelParams
from .spans import span
from .sweep import (CompiledBundle, MultiSweepResult, ParamGrid, SweepResult,
                    _sweep_plan, _sweep_plan_many, compile_bundle)
from .traces import TraceBundle


def _lower(obj, get_advisor) -> TraceBundle | CompiledBundle:
    """Lower ONE pricing subject to a (compiled) bundle."""
    if isinstance(obj, (TraceBundle, CompiledBundle)):
        return obj
    if isinstance(obj, str):
        from .advisor import synthesize_bundle
        adv = get_advisor()
        return synthesize_bundle(obj, {}, adv.params, adv.spec)
    if hasattr(obj, "as_text"):
        from ..compat import normalize_cost_analysis
        from .advisor import synthesize_bundle
        adv = get_advisor()
        return synthesize_bundle(obj.as_text(), normalize_cost_analysis(obj),
                                 adv.params, adv.spec)
    raise TypeError(
        f"cannot price a {type(obj).__name__}: expected a TraceBundle, "
        "CompiledBundle, HLO text, a compiled artifact with .as_text(), a "
        "sequence/mapping of those, or a serve engine with "
        ".compiled_steps()")


def _as_scenarios(scenarios):
    """Accept any ScenarioSet; a plain iterable of ``ModelParams`` is
    wrapped via ``ParamGrid.from_params`` as sugar."""
    if hasattr(scenarios, "view") and hasattr(scenarios, "labels"):
        return scenarios
    if isinstance(scenarios, ModelParams):
        return ParamGrid.from_params([scenarios])
    try:
        return ParamGrid.from_params(scenarios)
    except TypeError:
        raise TypeError(
            f"scenarios must be a ScenarioSet (e.g. a ParamGrid) or an "
            f"iterable of ModelParams, got {type(scenarios).__name__}"
        ) from None


def price(subject, scenarios, plan: ExecPlan | str | None = None,
          names=None, *, mpi_transfer=None, free_transfer=None,
          advisor=None) -> SweepResult | MultiSweepResult:
    """Price ``subject`` under every scenario of ``scenarios``.

    Dispatches on the subject type (see the module docstring for the full
    menu) and executes under ``plan`` — backend, chunking, vmap and
    Pallas options all live there; ``plan`` may also be the CLI string
    form (``"pallas:interpret=0"``).

    ``names`` labels the per-bundle results of a multi-subject price
    (mapping subjects: selects/reorders the keys).  ``mpi_transfer`` /
    ``free_transfer`` are the explicit transfer-model overrides of the
    legacy ``sweep_run``; ``advisor`` supplies the ``CommAdvisor`` used
    to synthesize bundles from HLO/compiled subjects (defaults to
    ``CommAdvisor()``).

    Returns a ``SweepResult`` for a single subject, a ``MultiSweepResult``
    for collections / mappings / engines.  A STREAMING backend (e.g.
    ``plan=ExecPlan.parse("distributed:devices=4,topk=64")``) instead
    returns its reduced :class:`~repro.core.sweep.TopKSweepResult` — the
    k best scenarios with exact per-call detail plus whole-sweep
    aggregates, never the full matrices — and only prices single
    subjects.
    """
    if isinstance(plan, str):
        plan = ExecPlan.parse(plan)
    grid = _as_scenarios(scenarios)

    _cache = [advisor]

    def get_advisor():
        if _cache[0] is None:
            from .advisor import CommAdvisor
            _cache[0] = CommAdvisor()
        return _cache[0]

    single = isinstance(subject, (TraceBundle, CompiledBundle, str)) \
        or hasattr(subject, "as_text")
    backend = plan.backend if plan is not None else ExecPlan.backend
    if single:
        if names is not None:
            raise ValueError("names= labels multi-subject pricing; this "
                             "subject prices to a single SweepResult")
        with span("repro.price", backend=backend, bundles=1,
                  scenarios=len(grid)):
            cb = _lower(subject, get_advisor)
            if isinstance(cb, TraceBundle):
                cb = compile_bundle(cb)
            return _sweep_plan(cb, grid, plan, mpi_transfer, free_transfer)

    if hasattr(subject, "compiled_steps"):           # serve engine
        subject = subject.compiled_steps()
    if isinstance(subject, Mapping):
        keys = tuple(names) if names is not None else tuple(subject)
        items = [subject[k] for k in keys]
        names = keys
    elif isinstance(subject, Sequence) or hasattr(subject, "__iter__"):
        items = list(subject)
    else:
        return _lower(subject, get_advisor)          # raises the TypeError
    with span("repro.price", backend=backend, bundles=len(items),
              scenarios=len(grid)):
        bundles = [_lower(it, get_advisor) for it in items]
        return _sweep_plan_many(bundles, grid, plan, names,
                                mpi_transfer, free_transfer)
