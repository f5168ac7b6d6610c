"""IR-tier static analysis: jaxpr/HLO dataflow checks over registered
entry points.

``python -m repro.analysis.ircheck`` is the second analysis tier next to
the AST linter (``repro.lint``): where the linter sees Python syntax,
this checker traces and lowers the repo's REPRESENTATIVE jitted entry
points (sweep kernels, serve steps, the train step) and inspects the IR
that actually runs:

jaxpr passes
  * ``peak-live-bytes`` — a liveness-based estimate of the largest set of
    simultaneously-live intermediate bytes, compared against the
    per-entry budget committed in ``IRCHECK_baseline.json`` (growth is a
    loud CI diff, not a silent drift — the same static-footprint quantity
    the memory-pooling literature prices).
  * ``f64-promotion`` — entries declared ``x64=False`` are re-traced
    under a scoped-x64 context and any equation that turns a <=32-bit
    float input into a float64/complex128 output is flagged: code that is
    only f32-correct because the ambient config canonicalizes f64 away
    breaks silently the moment anything enables x64.
  * ``host-callback`` — callback primitives and jaxpr effects not named
    by the entry's ``allow_effects`` (a host round-trip inside a hot
    jitted step is a sync + transfer per call).

HLO passes (built on :mod:`repro.core.hlo`)
  * ``donation-dead`` — parses ``input_output_alias`` from the compiled
    module and fails when a declared ``donate_argnums`` produced NO alias
    for any of that argument's flattened parameters (the donation
    silently bought nothing; the serve engine's two pool-donating jits
    are the prime targets).
  * ``collective-mesh`` — replica-group sizes of every collective must be
    a product of the entry's registered mesh axis sizes; single-member
    collectives are flagged as degenerate (pure overhead).
  * ``layout-churn`` — loop-corrected ``copy``/``transpose`` bytes,
    budgeted per entry in the baseline like peak-live-bytes.

Entry points live in an open registry — :func:`register_entrypoint`
mirrors ``repro.analysis.lint.register_rule`` and
``repro.core.execplan.register_backend`` — and each registration is a
LAZY builder returning an :class:`EntrySpec` (args as
``jax.ShapeDtypeStruct``\\ s: everything is traced/lowered, nothing is
executed).  Builtin entries self-register from their owning modules
(``repro.core.sweep_kernel``, ``repro.serve.paged``,
``repro.launch.train``) via a ``register_ircheck_entrypoints(register)``
hook, so the checker never hard-codes their configurations.

Findings use the same ``file:line rule message`` / nonzero-exit contract
as ``repro.lint``.  Known estimator limits: parameter numbering assumes
every argument leaf is used (``jit`` drops unused parameters), and
peak-live-bytes is a schedule-free upper-bound walk, not an XLA buffer
assignment — which is exactly why budgets carry a slack factor.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import inspect
import json
import math
import sys
from dataclasses import dataclass, field
from itertools import combinations
from pathlib import Path
from typing import Callable

from .lint import Finding

#: Default tolerance when comparing measured metrics against the
#: committed baseline: lowering drift across JAX versions moves the
#: numbers a little, a regression moves them a lot.
DEFAULT_SLACK = 0.25

#: Repo root (ircheck.py lives at src/repro/analysis/) — where the
#: default ``IRCHECK_baseline.json`` is committed.
REPO_ROOT = Path(__file__).resolve().parents[3]
BASELINE_NAME = "IRCHECK_baseline.json"

#: Primitives that round-trip to the host from inside a jitted program.
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "py_callback", "host_callback_call", "outside_call", "debug_print"})


# --------------------------------------------------------------------------
# Entry-point registry
# --------------------------------------------------------------------------

@dataclass
class EntrySpec:
    """One traced configuration of a jitted entry point.

    ``fn`` is either a plain callable (ircheck wraps it in ``jax.jit``
    with ``donate_argnums``) or an already-jitted object (anything with a
    ``.lower`` method — e.g. the serve engine's ``self._decode_paged``; then
    ``donate_argnums`` must restate what the jit was built with, for the
    donation pass).  ``args``/``kwargs`` are abstract values
    (``jax.ShapeDtypeStruct`` pytrees) or small concrete arrays — either
    way the entry is only traced and lowered, never executed.

    ``mesh_axes`` maps mesh axis names to sizes (or pass a ``Mesh``;
    ``repro.launch.mesh.mesh_axis_sizes`` normalizes it) and drives the
    collective audit.  ``x64=True`` traces/lowers under the scoped
    ``repro.compat.enable_x64`` context (and exempts the entry from the
    promotion pass — f64 is deliberate there).  ``min_devices`` skips the
    entry when the process has fewer devices than the configuration
    shards over.  ``allow_effects`` are substrings matched against
    callback primitive names and jaxpr effects the entry legitimately
    carries.  ``src`` is the reported ``path:line``; empty means
    introspect it from ``fn``.
    """

    name: str
    fn: Callable
    args: tuple = ()
    kwargs: dict = field(default_factory=dict)
    donate_argnums: tuple = ()
    mesh_axes: dict | None = None
    x64: bool = False
    min_devices: int = 1
    allow_effects: tuple = ()
    src: str = ""


_ENTRYPOINTS: dict = {}
_BUILTINS_LOADED = False

#: Modules owning builtin entry points; each exposes
#: ``register_ircheck_entrypoints(register)`` and registers its own
#: representative configurations (lazy builders, so importing ircheck
#: never traces anything).
_BUILTIN_PROVIDERS = ("repro.core.sweep_kernel", "repro.serve.paged",
                      "repro.launch.train")


def register_entrypoint(name: str, builder=None, *, min_devices: int = 1,
                        overwrite: bool = False):
    """Register a lazy :class:`EntrySpec` builder under ``name``.

    ``builder`` is a zero-argument callable returning an
    :class:`EntrySpec` (built on demand — heavy imports and model
    construction belong inside it).  Usable directly
    (``register_entrypoint("sweep.x", build)``) or as a decorator
    (``@register_entrypoint("sweep.x")``).  ``min_devices`` gates the
    BUILDER too: on a process with fewer devices the entry reports
    ``skipped`` without ever constructing the spec (a sharded builder may
    need the mesh to exist).  Re-registering raises unless
    ``overwrite=True`` — the same contract as ``register_rule`` /
    ``register_backend``.
    """
    def add(b):
        if not overwrite and name in _ENTRYPOINTS:
            raise ValueError(f"ircheck entry point {name!r} is already "
                             "registered (pass overwrite=True)")
        _ENTRYPOINTS[name] = (b, int(min_devices))
        return b
    return add if builder is None else add(builder)


def _load_builtins() -> None:
    global _BUILTINS_LOADED
    if _BUILTINS_LOADED:
        return
    _BUILTINS_LOADED = True
    import importlib
    for mod_name in _BUILTIN_PROVIDERS:
        mod = importlib.import_module(mod_name)
        mod.register_ircheck_entrypoints(register_entrypoint)


def known_entrypoints() -> tuple:
    """Sorted names of every registered entry point (builtins loaded)."""
    _load_builtins()
    return tuple(sorted(_ENTRYPOINTS))


# --------------------------------------------------------------------------
# jaxpr utilities (duck-typed: no jax.core imports — the Jaxpr/Var homes
# drift across JAX versions, their attribute surface does not)
# --------------------------------------------------------------------------

def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except (TypeError, ValueError):     # dynamic/polymorphic dim
            return 0
    return n * getattr(dtype, "itemsize", 0)


def _is_literal(v) -> bool:
    return hasattr(v, "val")


def _iter_subjaxprs(val):
    """Yield raw jaxprs reachable from one eqn param value."""
    if hasattr(val, "eqns") and hasattr(val, "invars"):
        yield val
    elif hasattr(val, "jaxpr"):                       # ClosedJaxpr
        yield from _iter_subjaxprs(val.jaxpr)
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _iter_subjaxprs(item)


def _eqn_subjaxprs(eqn):
    for val in eqn.params.values():
        yield from _iter_subjaxprs(val)


def iter_eqns(jaxpr):
    """Every equation of ``jaxpr`` and (recursively) its subjaxprs."""
    j = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in j.eqns:
        yield eqn
        for sub in _eqn_subjaxprs(eqn):
            yield from iter_eqns(sub)


def _aliased_out_bytes(eqn) -> int:
    """Bytes of ``eqn`` outputs that alias its loop carries: a ``while``
    output *is* its carry's final value, and ``scan``'s first
    ``num_carry`` outputs are the carries.  While the body runs those
    outputs occupy no buffer of their own, so the body-peak candidate
    must not count them on top of the carry inputs."""
    name = eqn.primitive.name
    if name == "while":
        outs = eqn.outvars
    elif name == "scan":
        outs = eqn.outvars[:eqn.params.get("num_carry", 0)]
    else:
        return 0
    return sum(_aval_bytes(v.aval) for v in outs if not _is_literal(v))


def peak_live_bytes(jaxpr) -> int:
    """Schedule-free peak of simultaneously-live bytes over the jaxpr.

    A last-use liveness walk in program order: inputs + consts are live
    from the start, each equation's outputs become live when defined, and
    a value dies after the equation of its last use (jaxpr outputs live
    to the end).  Control-flow bodies contribute their own inner peak
    MINUS their input bytes (those are already counted live outside),
    and for ``while``/``scan`` the body-peak candidate also drops the
    equation's carry-aliased outputs (:func:`_aliased_out_bytes`) — the
    loop's result buffers are its carries, not extra allocations, so
    carries + body temporaries are counted living together exactly once.
    Still an upper-bound estimator, not XLA's buffer assignment, which
    is why the committed budgets carry slack.
    """
    j = getattr(jaxpr, "jaxpr", jaxpr)
    eqns = list(j.eqns)
    n = len(eqns)

    last_use: dict = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last_use[v] = i
    for v in j.outvars:
        if not _is_literal(v):
            last_use[v] = n

    live = 0
    for v in tuple(j.invars) + tuple(j.constvars):
        live += _aval_bytes(v.aval)
    peak = live

    for i, eqn in enumerate(eqns):
        inner_extra = 0
        for sub in _eqn_subjaxprs(eqn):
            sub_in = sum(_aval_bytes(v.aval) for v in sub.invars)
            inner_extra = max(inner_extra,
                              peak_live_bytes(sub) - sub_in)
        defined = {v for v in eqn.outvars if not _is_literal(v)}
        for v in defined:
            live += _aval_bytes(v.aval)
        alias_b = _aliased_out_bytes(eqn) if inner_extra > 0 else 0
        peak = max(peak, live, live + inner_extra - alias_b)
        dying = {v for v in eqn.invars
                 if not _is_literal(v) and last_use.get(v) == i}
        dying |= {v for v in defined if v not in last_use}
        for v in dying:
            live -= _aval_bytes(v.aval)
    return peak


def f64_promotions(jaxpr) -> dict:
    """``{primitive name: count}`` of equations that take a <=32-bit
    float input and produce a float64/complex128 output — the silent
    promotion points an ``x64=False`` entry must not contain."""
    wide = ("float64", "complex128")
    narrow = ("float32", "float16", "bfloat16")
    out: dict = {}
    for eqn in iter_eqns(jaxpr):
        dtypes_in = {str(getattr(v.aval, "dtype", "")) for v in eqn.invars}
        if not dtypes_in.intersection(narrow):
            continue
        for v in eqn.outvars:
            if str(getattr(v.aval, "dtype", "")) in wide:
                name = eqn.primitive.name
                out[name] = out.get(name, 0) + 1
                break
    return out


def callback_audit(jaxpr, allow_effects=()) -> list:
    """Callback primitives + jaxpr effects not covered by
    ``allow_effects`` substrings; returns ``[(kind, detail), ...]``."""
    def allowed(s: str) -> bool:
        return any(pat in s for pat in allow_effects)

    hits = []
    seen_prims = set()
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMS and name not in seen_prims \
                and not allowed(name):
            seen_prims.add(name)
            hits.append(("primitive", name))
    for eff in getattr(jaxpr, "effects", ()) or ():
        s = str(eff)
        if not allowed(s):
            hits.append(("effect", s))
    return hits


# --------------------------------------------------------------------------
# HLO pass helpers
# --------------------------------------------------------------------------

def dead_donations(text: str, donate_argnums, args) -> list:
    """Donated argnums whose flattened parameters have NO
    ``input_output_alias`` entry in the compiled module.

    ``jit`` numbers HLO parameters by the flattened leaf order of the
    positional arguments, so argnum ``i`` owns the contiguous leaf range
    after argnums ``0..i-1`` (every leaf assumed used — the documented
    ``keep_unused`` caveat).
    """
    if not donate_argnums:
        return []
    from ..core.hlo import input_output_aliases
    import jax
    counts = [len(jax.tree_util.tree_leaves(a)) for a in args]
    offsets = [0]
    for c in counts:
        offsets.append(offsets[-1] + c)
    aliased = {param for _, param, _ in input_output_aliases(text)}
    dead = []
    for argnum in donate_argnums:
        if not 0 <= argnum < len(counts):
            dead.append((argnum, 0))
            continue
        rng = range(offsets[argnum], offsets[argnum + 1])
        if not any(p in aliased for p in rng):
            dead.append((argnum, len(rng)))
    return dead


def collective_findings(text: str, mesh_axes: dict | None) -> list:
    """``(message,)`` strings for collectives whose replica groups don't
    match the registered mesh, plus degenerate single-member groups."""
    from ..core.hlo import parse_collectives
    ops = parse_collectives(text, correct_cpu_f32=False)
    if not ops:
        return []
    msgs = []
    valid: set = set()
    if mesh_axes:
        sizes = [int(s) for s in mesh_axes.values()]
        for r in range(1, len(sizes) + 1):
            for combo in combinations(sizes, r):
                valid.add(math.prod(combo))
    for op in ops:
        where = f"{op.kind} {op.name!r} in {op.computation!r}"
        if op.group_size <= 1:
            msgs.append(f"degenerate single-member {where}: the collective "
                        "moves no data but still pays launch/sync overhead")
        elif mesh_axes is None:
            msgs.append(f"{where} has replica groups of {op.group_size} but "
                        "the entry registered no mesh (pass mesh_axes= so "
                        "group sizes can be cross-checked)")
        elif op.group_size not in valid:
            axes = ", ".join(f"{k}={v}" for k, v in mesh_axes.items())
            msgs.append(f"{where} spans {op.group_size} members — not a "
                        f"product of the registered mesh axes ({axes})")
    return msgs


# --------------------------------------------------------------------------
# Per-entry driver
# --------------------------------------------------------------------------

@dataclass
class EntryReport:
    """The checker's result for one entry point."""

    name: str
    status: str                   # "ok" | "findings" | "skipped" | "error"
    findings: list = field(default_factory=list)
    metrics: dict = field(default_factory=dict)
    note: str = ""

    def as_dict(self) -> dict:
        return {"name": self.name, "status": self.status,
                "note": self.note, "metrics": self.metrics,
                "findings": [dataclasses.asdict(f) for f in self.findings]}


def src_for(fn) -> str:
    """Repo-root-relative ``path:line`` of a plain function — for
    providers registering wrapped callables (``shard_map`` products,
    nested jits) whose source would not introspect from the wrapper."""
    try:
        path = Path(inspect.getsourcefile(fn) or "")
        line = inspect.getsourcelines(fn)[1]
    except (OSError, TypeError):
        return ""
    try:
        path = path.resolve().relative_to(REPO_ROOT)
    except ValueError:
        pass
    return f"{str(path).replace(chr(92), '/')}:{line}"


def _src_of(spec: EntrySpec) -> tuple:
    """``(path, line)`` findings are reported at."""
    if spec.src:
        path, _, line = spec.src.rpartition(":")
        if path and line.isdigit():
            return path, int(line)
        return spec.src, 0
    fn = spec.fn
    for _ in range(8):                      # unwrap jit/partial layers
        inner = getattr(fn, "__wrapped__", None) or getattr(fn, "func", None)
        if inner is None:
            break
        fn = inner
    try:
        path = Path(inspect.getsourcefile(fn) or "")
        line = inspect.getsourcelines(fn)[1]
    except (OSError, TypeError):
        return "<unknown>", 0
    try:
        path = path.resolve().relative_to(REPO_ROOT)
    except ValueError:
        pass
    return str(path).replace("\\", "/"), line


def _x64_scope(on: bool):
    if on:
        from ..compat import enable_x64
        return enable_x64()
    return contextlib.nullcontext()


def _mesh_axes_of(spec: EntrySpec) -> dict | None:
    m = spec.mesh_axes
    if m is None or isinstance(m, dict):
        return m
    from ..launch.mesh import mesh_axis_sizes
    return mesh_axis_sizes(m)


def check_entry(spec: EntrySpec, baseline_entry: dict | None = None,
                slack: float = DEFAULT_SLACK) -> EntryReport:
    """Run every pass over ONE entry spec.

    ``baseline_entry`` is this entry's dict from ``IRCHECK_baseline.json``
    (``None`` skips the budget comparisons, e.g. for ad-hoc user specs);
    a measured metric may exceed its recorded budget by at most
    ``slack`` (relative) before it becomes a finding.
    """
    import functools
    import jax

    path, line = _src_of(spec)
    rep = EntryReport(name=spec.name, status="ok")

    def finding(rule: str, message: str) -> None:
        rep.findings.append(Finding(path, line, rule,
                                    f"[{spec.name}] {message}"))

    if jax.device_count() < spec.min_devices:
        rep.status = "skipped"
        rep.note = (f"needs {spec.min_devices} devices, have "
                    f"{jax.device_count()} (set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count="
                    f"{spec.min_devices})")
        return rep

    traced = spec.fn           # make_jaxpr traces plain AND jitted fns
    try:
        with _x64_scope(spec.x64):
            jitted = traced if hasattr(traced, "lower") else \
                jax.jit(traced, donate_argnums=spec.donate_argnums)
            closed = jax.make_jaxpr(functools.partial(
                traced, **spec.kwargs))(*spec.args)
            text = jitted.lower(*spec.args,
                                **spec.kwargs).compile().as_text()
    except Exception as e:                                 # noqa: BLE001
        rep.status = "error"
        rep.note = f"{type(e).__name__}: {e}"
        finding("entry-error", f"trace/compile failed: {rep.note}")
        return rep

    # ---- jaxpr passes -----------------------------------------------------
    peak = peak_live_bytes(closed)
    rep.metrics["peak_live_bytes"] = int(peak)

    if not spec.x64:
        try:
            with _x64_scope(True):
                closed_x64 = jax.make_jaxpr(functools.partial(
                    traced, **spec.kwargs))(*spec.args)
            for prim, count in sorted(f64_promotions(closed_x64).items()):
                finding("f64-promotion",
                        f"{count} {prim!r} equation(s) promote <=32-bit "
                        "float inputs to float64 under x64 — pin the "
                        "constant/op dtype (the ambient f32 config only "
                        "masks this)")
        except Exception as e:                             # noqa: BLE001
            finding("entry-error",
                    f"x64 re-trace for the promotion pass failed: "
                    f"{type(e).__name__}: {e}")

    for kind, detail in callback_audit(closed, spec.allow_effects):
        finding("host-callback",
                f"jitted entry carries host {kind} {detail!r} (a sync + "
                "transfer per call); allow_effects= it if deliberate")

    # ---- HLO passes -------------------------------------------------------
    from ..core.hlo import layout_churn_bytes
    for argnum, n_leaves in dead_donations(text, spec.donate_argnums,
                                           spec.args):
        finding("donation-dead",
                f"donate_argnums={spec.donate_argnums} declared argnum "
                f"{argnum} donated but none of its {n_leaves} "
                "parameter(s) appear in input_output_alias — the donation "
                "bought nothing (shape/dtype mismatch between the donated "
                "input and the output it should alias?)")

    for msg in collective_findings(text, _mesh_axes_of(spec)):
        finding("collective-mesh", msg)

    churn = layout_churn_bytes(text)
    rep.metrics["copy_transpose_bytes"] = int(churn)

    # ---- baseline budgets -------------------------------------------------
    if baseline_entry is not None:
        for metric, rule in (("peak_live_bytes", "peak-live-bytes"),
                             ("copy_transpose_bytes", "layout-churn")):
            measured = rep.metrics[metric]
            budget = baseline_entry.get(metric)
            if budget is None:
                finding("baseline-missing",
                        f"no {metric} budget recorded in {BASELINE_NAME} "
                        "(run with --write-baseline to record it)")
            elif measured > budget * (1.0 + slack):
                finding(rule,
                        f"{metric} grew to {measured:,} bytes, over the "
                        f"committed budget {budget:,} (+{slack:.0%} slack)"
                        " — rebaseline deliberately with --write-baseline "
                        "or fix the regression")

    if rep.findings:
        rep.status = "findings"
    return rep


def check_entrypoints(names=None, baseline: dict | None = None,
                      slack: float | None = None) -> list:
    """Run the checker over the named (default: all) registered entry
    points -> list of :class:`EntryReport`.  ``baseline`` is the parsed
    ``IRCHECK_baseline.json`` dict (``None`` disables budgets)."""
    _load_builtins()
    all_names = known_entrypoints()
    if names:
        unknown = sorted(set(names) - set(all_names))
        if unknown:
            raise ValueError(f"unknown entry point(s) {unknown} "
                             f"(registered: {', '.join(all_names)})")
        run_names = [n for n in all_names if n in set(names)]
    else:
        run_names = list(all_names)
    entries = (baseline or {}).get("entries", {})
    if slack is None:
        slack = float((baseline or {}).get("slack", DEFAULT_SLACK))
    import jax
    reports = []
    for name in run_names:
        builder, min_dev = _ENTRYPOINTS[name]
        if jax.device_count() < min_dev:
            reports.append(EntryReport(
                name=name, status="skipped",
                note=f"needs {min_dev} devices, have {jax.device_count()} "
                     "(set XLA_FLAGS=--xla_force_host_platform_device_"
                     f"count={min_dev})"))
            continue
        spec = builder()
        base = entries.get(name) if baseline is not None else None
        reports.append(check_entry(spec, baseline_entry=base, slack=slack))
    return reports


# --------------------------------------------------------------------------
# Baseline I/O + CLI
# --------------------------------------------------------------------------

def load_baseline(path) -> dict | None:
    path = Path(path)
    if not path.exists():
        return None
    return json.loads(path.read_text())


def write_baseline(path, reports, slack: float) -> dict:
    """Merge the measured metrics of checked entries into the baseline
    file (skipped/errored entries keep their previous budgets)."""
    path = Path(path)
    base = load_baseline(path) or {}
    entries = dict(base.get("entries", {}))
    for rep in reports:
        if rep.metrics:
            entries[rep.name] = {k: rep.metrics[k]
                                 for k in sorted(rep.metrics)}
    out = {"slack": slack, "entries": {k: entries[k]
                                       for k in sorted(entries)}}
    path.write_text(json.dumps(out, indent=2) + "\n")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.ircheck",
        description="IR-tier static analysis over registered jitted entry "
                    "points (jaxpr liveness/promotion/callback passes + "
                    "HLO donation/collective/layout passes); exits nonzero "
                    "on findings")
    ap.add_argument("--entry", action="append", default=None,
                    help="check only this entry point (repeatable)")
    ap.add_argument("--baseline", default=str(REPO_ROOT / BASELINE_NAME),
                    help=f"budget file (default: {BASELINE_NAME} at the "
                         "repo root)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="record measured metrics as the new budgets "
                         "instead of comparing against them")
    ap.add_argument("--slack", type=float, default=None,
                    help="relative budget tolerance (default: the "
                         f"baseline file's, else {DEFAULT_SLACK})")
    ap.add_argument("--format", choices=("text", "json"), default="text",
                    help="findings as text lines (default) or one JSON "
                         "report for CI artifacts")
    ap.add_argument("--list", action="store_true",
                    help="print the registered entry points and exit")
    args = ap.parse_args(argv)

    if args.list:
        for name in known_entrypoints():
            print(name)
        return 0

    baseline = None if args.write_baseline else load_baseline(args.baseline)
    if baseline is None and not args.write_baseline:
        print(f"warning: no baseline at {args.baseline} — budget passes "
              "disabled (run --write-baseline to create it)",
              file=sys.stderr)
    try:
        reports = check_entrypoints(args.entry, baseline=baseline,
                                    slack=args.slack)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    if args.write_baseline:
        slack = args.slack if args.slack is not None else \
            float((load_baseline(args.baseline) or {}).get(
                "slack", DEFAULT_SLACK))
        write_baseline(args.baseline, reports, slack)
        print(f"wrote {args.baseline}", file=sys.stderr)

    findings = [f for r in reports for f in r.findings]
    if args.format == "json":
        print(json.dumps({"tool": "repro.analysis.ircheck",
                          "n_findings": len(findings),
                          "entries": [r.as_dict() for r in reports]},
                         indent=2))
    else:
        for f in findings:
            print(f)
        for r in reports:
            extra = f" ({r.note})" if r.note else ""
            metrics = ", ".join(f"{k}={v:,}"
                                for k, v in sorted(r.metrics.items()))
            print(f"ircheck: {r.name:28s} {r.status:9s} "
                  f"{metrics}{extra}", file=sys.stderr)
    n_skip = sum(r.status == "skipped" for r in reports)
    print(f"ircheck: {len(findings)} finding(s) across {len(reports)} "
          f"entry point(s), {n_skip} skipped", file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
