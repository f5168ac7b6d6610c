"""ExecPlan — one frozen value object for ALL sweep-execution config.

Four PRs of sweep work grew four hand-plumbed execution kwargs
(``backend=``, ``chunk_scenarios=``, ``vmap_scenarios=``,
``pallas_interpret=``) threaded through ``sweep_run`` / ``sweep_run_many``
/ every ``CommAdvisor.sweep_*`` method / scripts / benchmarks, with the
backend name validated independently in three places.  This module is the
single source of truth that replaces all of that:

  * :class:`ExecPlan` — a frozen dataclass holding the full execution
    config.  Construct once, pass everywhere:
    ``price(cb, grid, plan=ExecPlan(backend="pallas", chunk_scenarios=8))``.
  * the **backend registry** — :func:`register_backend` maps a name to an
    executor ``fn(compiled_bundle, view, plan) -> {field: matrix}``
    (:data:`repro.core.sweep_kernel.MATRIX_FIELDS` keys).  The numpy /
    jax / pallas builtins register themselves here; adding a backend is
    one ``register_backend`` call — no if/elif ladder to extend.
  * :meth:`ExecPlan.parse` — the CLI-string form
    (``"jax"``, ``"pallas:interpret=0,chunk=8"``), the single place
    scripts validate ``--backend`` arguments.

Legacy-kwarg migration: :func:`legacy_plan` converts the deprecated
per-call kwargs into an ``ExecPlan`` while emitting exactly one
``DeprecationWarning`` — the shims in ``sweep`` and ``advisor`` all route
through it.
"""
from __future__ import annotations

import dataclasses
import warnings
from dataclasses import dataclass, replace
from typing import Callable

from .sweep_kernel import (pallas_modes, price_grid_jax, price_grid_numpy,
                           price_grid_pallas)

#: Sentinel distinguishing "kwarg not passed" from any real value in the
#: deprecated ``sweep_run(backend=...)``-style signatures.
_UNSET = type("_Unset", (), {"__repr__": lambda self: "<unset>"})()

_BACKENDS: dict[str, Callable] = {}
_STREAMING: set = set()


def register_backend(name: str, fn: Callable, *, streaming: bool = False,
                     overwrite: bool = False):
    """Register a sweep executor under ``name``.

    A MATRIX backend (the default) is
    ``fn(cb, view, plan) -> {field: matrix}`` for every ``MATRIX_FIELDS``
    key, each broadcastable to ``(n_scenarios, n_calls)``, as a host
    (numpy) or a device (jax) array; the execution core wraps it with
    scenario-axis chunking and builds a full ``SweepResult``.  The core,
    not the backend, copies device outputs to the host and widens them to
    float64, so a backend returns its results where and as it computed
    them.

    A STREAMING backend (``streaming=True``) owns its whole execution:
    ``fn(cb, scenarios, plan, mpi_transfer, free_transfer)`` receives the
    :class:`~repro.core.sweep.ScenarioSet` itself (not a view — it
    chunks, shards and pads internally) and returns a reduced result
    (canonically a :class:`~repro.core.sweep.TopKSweepResult`) WITHOUT
    ever materializing the full ``(S, n_calls)`` matrices.  The builtin
    ``"distributed"`` executor is one.

    Registering an existing name raises unless ``overwrite=True``.
    """
    if not overwrite and name in _BACKENDS:
        raise ValueError(f"backend {name!r} is already registered "
                         "(pass overwrite=True to replace it)")
    _BACKENDS[name] = fn
    _STREAMING.discard(name)
    if streaming:
        _STREAMING.add(name)
    return fn


def known_backends() -> tuple:
    """Sorted names of every registered sweep backend."""
    return tuple(sorted(_BACKENDS))


def is_streaming(name: str) -> bool:
    """Whether ``name`` was registered as a streaming backend (returns a
    reduced top-k result instead of full component matrices)."""
    return name in _STREAMING


def resolve_backend(name: str) -> Callable:
    """Look up a registered executor; unknown names raise the one
    canonical usage error (scripts surface it verbatim)."""
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r} (registered: "
            f"{', '.join(known_backends())})") from None


@dataclass(frozen=True)
class ExecPlan:
    """How to execute a scenario sweep — everything except the physics.

    Fields:
      * ``backend`` — a :func:`register_backend` name (builtins:
        ``"numpy"``, ``"jax"``, ``"pallas"``).
      * ``chunk_scenarios`` — evaluate the grid in scenario-axis chunks of
        this size; peak intermediates drop to ``O(chunk x n_samples)``
        with bit-identical results.  ``None`` = one pass.
      * ``vmap_scenarios`` — (jax only) ``jax.vmap`` the per-scenario
        kernel instead of the broadcasted batch formulation.
      * ``pallas_interpret`` — (pallas only) run the kernel body in the
        Pallas interpreter.  ``None`` follows the platform: compiled
        Mosaic on TPU, interpreted elsewhere.
      * ``x64`` — scope the evaluation to double precision via
        ``repro.compat.enable_x64``; ``False`` prices in float32.
        ``None`` is float64 except for the compiled Pallas kernel, which
        has no float64 and prices in float32.
      * ``devices`` — (distributed only) shard the scenario axis over this
        many devices (``None`` = all visible devices).
      * ``topk`` — (streaming backends) how many best-by-speedup scenarios
        survive the streaming reduction (full rows kept for exactly
        these).
      * ``refine`` — (distributed + a refinable ScenarioSet) number of
        adaptive frontier-refinement rounds appended after the seed set;
        each round re-samples ``len(seed)`` scenarios around the current
        speedup frontier.

    :meth:`resolved` fills in ``pallas_interpret`` and ``x64``; every
    executor receives a resolved plan, and a matrix sweep records it on its
    ``SweepResult``.
    """

    backend: str = "numpy"
    chunk_scenarios: int | None = None
    vmap_scenarios: bool = False
    pallas_interpret: bool | None = None
    x64: bool | None = None
    devices: int | None = None
    topk: int = 64
    refine: int = 0

    def __post_init__(self):
        if self.chunk_scenarios is not None and self.chunk_scenarios < 1:
            raise ValueError("chunk_scenarios must be >= 1, got "
                             f"{self.chunk_scenarios}")
        if self.vmap_scenarios and self.backend != "jax":
            raise ValueError("vmap_scenarios requires backend='jax'")
        if self.devices is not None and self.devices < 1:
            raise ValueError(f"devices must be >= 1, got {self.devices}")
        if self.topk < 1:
            raise ValueError(f"topk must be >= 1, got {self.topk}")
        if self.refine < 0:
            raise ValueError(f"refine must be >= 0, got {self.refine}")

    def resolved(self) -> "ExecPlan":
        """This plan with ``pallas_interpret`` / ``x64`` decided for the
        platform the sweep runs on (see the field docs)."""
        if self.backend == "pallas":
            interpret, x64 = pallas_modes(self.pallas_interpret, self.x64)
            return replace(self, pallas_interpret=interpret, x64=x64)
        return replace(self, x64=True if self.x64 is None else self.x64)

    def executor(self) -> Callable:
        """The registered ``fn(cb, view, plan)`` for :attr:`backend`."""
        return resolve_backend(self.backend)

    def replace(self, **kw) -> "ExecPlan":
        return replace(self, **kw)

    #: CLI option spellings accepted by :meth:`parse` (``int`` converter =
    #: integer opt, ``None`` = boolean ``0/1/true/false`` opt).  The dict
    #: order is also the canonical emission order of :meth:`to_string`.
    _PARSE_OPTS = {"chunk": ("chunk_scenarios", int),
                   "vmap": ("vmap_scenarios", None),
                   "interpret": ("pallas_interpret", None),
                   "x64": ("x64", None),
                   "devices": ("devices", int),
                   "topk": ("topk", int),
                   "refine": ("refine", int)}

    @classmethod
    def parse(cls, spec: str, **overrides) -> "ExecPlan":
        """Parse the CLI form ``"backend[:opt=val,...]"``.

        Examples: ``"jax"``, ``"numpy:chunk=8"``,
        ``"pallas:interpret=0,chunk=4"``, ``"jax:vmap=1"``.  Recognized
        opts: ``chunk`` (int), ``vmap`` / ``interpret`` / ``x64``
        (``0/1/true/false``).  The backend name is validated against the
        registry here — the single source of the unknown-backend usage
        message.  ``overrides`` are applied on top as ExecPlan fields;
        ``None`` overrides mean "not specified" and never clobber a
        spec-supplied option (so CLIs can pass their flag defaults
        straight through).
        """
        spec = (spec or "").strip()
        name, sep, opts = spec.partition(":")
        resolve_backend(name)                  # canonical unknown-name error
        kw: dict = {"backend": name}
        seen: set = set()
        for item in ([s.strip() for s in opts.split(",")] if sep else []):
            if not item:
                raise ValueError(
                    f"empty option segment in {spec!r} "
                    f"(expected backend[:opt=val,...], e.g. "
                    f"{name}:chunk=8)")
            key, eq, val = item.partition("=")
            if key in seen:
                raise ValueError(
                    f"duplicate option {key!r} in {spec!r} "
                    f"(each opt may appear at most once)")
            seen.add(key)
            if key not in cls._PARSE_OPTS:
                raise ValueError(
                    f"unknown ExecPlan option {key!r} in {spec!r} "
                    f"(expected backend[:opt=val,...] with opts: "
                    f"{', '.join(sorted(cls._PARSE_OPTS))})")
            field, conv = cls._PARSE_OPTS[key]
            if conv is int:
                kw[field] = int(val) if eq else 1
            else:
                kw[field] = val.lower() not in ("0", "false", "no") \
                    if eq else True
        kw.update({k: v for k, v in overrides.items() if v is not None})
        return cls(**kw)

    def to_string(self) -> str:
        """The exact inverse of :meth:`parse`:
        ``ExecPlan.parse(p.to_string()) == p`` for every plan.

        Only non-default fields are emitted (``"numpy"`` stays
        ``"numpy"``), in the canonical ``_PARSE_OPTS`` order, booleans as
        ``0``/``1`` — so benchmark JSON and logs can record a plan in a
        form that round-trips through the CLI parser.
        """
        defaults = {f.name: f.default for f in dataclasses.fields(type(self))}
        opts = []
        for key, (fname, conv) in self._PARSE_OPTS.items():
            val = getattr(self, fname)
            if val == defaults[fname]:
                continue
            opts.append(f"{key}={int(val) if conv is None else val}")
        return self.backend + (":" + ",".join(opts) if opts else "")


def legacy_plan(plan, caller: str, **legacy) -> ExecPlan:
    """Resolve a shim's ``plan=`` argument against its deprecated
    execution kwargs (passed with the :data:`_UNSET` sentinel default).

    Explicit legacy kwargs emit exactly ONE ``DeprecationWarning`` and
    build the equivalent :class:`ExecPlan`; mixing them with ``plan=``
    raises.  A ``plan`` given as a string goes through
    :meth:`ExecPlan.parse`.
    """
    passed = {k: v for k, v in legacy.items() if v is not _UNSET}
    if passed:
        if plan is not None:
            raise ValueError(
                f"{caller}: pass plan=ExecPlan(...) OR the legacy "
                f"execution kwargs ({', '.join(sorted(passed))}), not both")
        warnings.warn(
            f"{caller}: the execution kwargs "
            f"({', '.join(sorted(passed))}) are deprecated; pass "
            "plan=ExecPlan(...) instead (see repro.core.ExecPlan)",
            DeprecationWarning, stacklevel=3)
        return ExecPlan(**passed)
    if plan is None:
        return ExecPlan()
    if isinstance(plan, str):
        return ExecPlan.parse(plan)
    return plan


# --------------------------------------------------------------------------
# Builtin executors (the registry entries the if/elif ladder used to be)
# --------------------------------------------------------------------------

def _run_numpy(cb, view, plan: ExecPlan) -> dict:
    return price_grid_numpy(cb, view)


def _run_jax(cb, view, plan: ExecPlan) -> dict:
    return price_grid_jax(cb, view, vmap_scenarios=plan.vmap_scenarios,
                          x64=plan.x64)


def _run_pallas(cb, view, plan: ExecPlan) -> dict:
    return price_grid_pallas(cb, view, interpret=plan.pallas_interpret,
                             x64=plan.x64)


def _run_distributed(cb, scenarios, plan: ExecPlan,
                     mpi_transfer=None, free_transfer=None):
    # lazy import: adaptive builds on sweep, which imports this module
    from .adaptive import run_distributed
    return run_distributed(cb, scenarios, plan, mpi_transfer=mpi_transfer,
                           free_transfer=free_transfer)


register_backend("numpy", _run_numpy)
register_backend("jax", _run_jax)
register_backend("pallas", _run_pallas)
register_backend("distributed", _run_distributed, streaming=True)
