"""Scenario-sweep engine: grids, bundle compilation, and result views.

The per-call predictor (``predictor.predict_run``) evaluates ONE
``ModelParams`` at a time through scalar math.  Mapping the latency /
bandwidth design space the related work measures (cMPI's one-/two-sided CXL
latencies, the 2-3x pooled-memory latency bands) needs hundreds of model
evaluations — so this module compiles a ``TraceBundle`` ONCE into packed
flat arrays and prices an entire grid of scenarios through the
backend-pluggable kernel in ``sweep_kernel``:

    cb     = compile_bundle(bundle)
    grid   = ParamGrid.product(ModelParams.multinode(),
                               cxl_lat_ns=[250, 300, 350, 400],
                               cxl_atomic_lat_ns=[350, 430, 550, 650],
                               mpi_transfer=["hockney", "loggp"])
    result = price(cb, grid)                            # one broadcasted pass
    result = price(cb, grid, plan=ExecPlan("jax"))      # jit'd, vmap-able
    result = price(cb, grid, plan=ExecPlan("pallas"))   # fused bracket kernel
    result = price(cb, grid,
                   plan=ExecPlan(chunk_scenarios=8))    # O(chunk) memory
    result.predicted_speedup()                          # per-scenario view

    multi = price([cb_a, cb_b], grid)                # MANY bundles, ONE pass
    multi["bundle1"].predicted_speedup()             # per-bundle SweepResult
    multi.predicted_speedup(weights={"bundle1": 8})  # deployment-level mix

(``sweep_run`` / ``sweep_run_many`` remain as thin shims over the same
cores; their per-call execution kwargs are deprecated in favour of
``plan=ExecPlan(...)``.)

Division of labour:

  * THIS module owns the data model — the :class:`ScenarioSet` protocol
    and ``ParamGrid``, its canonical implementation (factorial
    :meth:`ParamGrid.product`, Latin-hypercube / uniform
    :meth:`ParamGrid.sample`, paired :meth:`ParamGrid.zip`, union
    :meth:`ParamGrid.concat`; numeric axes over any ``ModelParams`` field
    PLUS categorical ``mpi_transfer=``/``free_transfer=`` axes that mix
    transfer models within one grid), ``compile_bundle``/
    ``CompiledBundle`` (trace -> packed arrays, both reduceat- and
    segment-id-encoded), ``SweepResult``, and the execution cores
    ``_sweep_plan``/``_sweep_plan_many`` that ``repro.core.price`` (the
    polymorphic front door in ``pricing``) drives.
  * ``execplan`` owns HOW a sweep executes — the frozen ``ExecPlan``
    config object and the ``register_backend`` registry the cores
    dispatch through.
  * ``sweep_kernel.price_grid(cb, view, xp)`` owns the evaluation — one
    pure, array-module-generic function executed by the NumPy backend
    (with scenario-axis chunking, bit-identical to unchunked), the
    ``jax.jit`` backend (``jax.ops.segment_sum`` via ``repro.compat``,
    optional ``vmap`` over the scenario axis), or the Pallas backend
    (``kernels/sweep_bracket`` fuses the bracket terms with the per-site
    segment reduction in VMEM; interpret mode on CPU).

The physics is NOT duplicated: the bracket formulas (Eq. 6-10) live in
``access.BracketTerms`` / ``access.category_bracket`` and the transfer
models expose ``transfer_from_traffic`` — every path calls the same code,
scalars in the per-call path, ``(n_scenarios, n_sites)`` arrays here.
"""
from __future__ import annotations

import dataclasses
import itertools
from dataclasses import dataclass
from typing import Protocol, runtime_checkable

import numpy as np

from .access import SampleArrays, prefetch_hit_fraction
from .execplan import (_UNSET, ExecPlan, is_streaming, legacy_plan,
                       resolve_backend)
from .params import ModelParams, Thresholds
from .predictor import CallPrediction
from .spans import span
from .sweep_kernel import MATRIX_FIELDS, SPEEDUP_HIST_EDGES
from .traces import TraceBundle
from .transfer import TRANSFER_MODELS, SiteTraffic


# --------------------------------------------------------------------------
# Parameter grids
# --------------------------------------------------------------------------

#: Categorical grid axes (not ``ModelParams`` fields): axis name -> the
#: default transfer-model name used when the axis is not swept.  Values must
#: be keys of ``transfer.TRANSFER_MODELS``.
CATEGORICAL_AXES = {"mpi_transfer": "hockney",
                    "free_transfer": "message_free"}


class _ThresholdView:
    """lower/upper pairs stacked across scenarios (no Thresholds validation —
    arrays have no single truth value)."""

    def __init__(self, lower, upper):
        self.lower = lower
        self.upper = upper


class _ParamArrays:
    """Duck-typed ``ModelParams`` whose every field is an ``(S, 1)`` array.

    The characterization / access / transfer code only does arithmetic on
    the fields, so this view flows through the exact same functions the
    scalar path uses — broadcasting turns their outputs into per-scenario
    arrays.  On top of the numeric fields it carries the categorical
    transfer-model axes: per side a static tuple of candidate models (each
    built from these same ``(S, 1)`` fields) and an ``(S, 1)`` integer code
    selecting one candidate per scenario.

    Registered as a jax pytree by ``sweep_kernel`` so the whole view is one
    donatable ``jit`` argument and ``vmap`` can map its scenario axis.
    """

    def __init__(self, params, cat=None):
        for f in dataclasses.fields(ModelParams):
            vals = [getattr(p, f.name) for p in params]
            if isinstance(vals[0], Thresholds):
                setattr(self, f.name, _ThresholdView(
                    np.array([t.lower for t in vals])[:, None],
                    np.array([t.upper for t in vals])[:, None]))
            else:
                setattr(self, f.name, np.array(vals, dtype=np.float64)[:, None])
        cat = cat or {}
        for axis, default in CATEGORICAL_AXES.items():
            names = cat.get(axis) or (default,) * len(params)
            cands = tuple(dict.fromkeys(names))   # order of first appearance
            idx = {n: k for k, n in enumerate(cands)}
            code = np.array([idx[n] for n in names], dtype=np.int32)[:, None]
            setattr(self, axis + "_code", code)
            setattr(self, axis + "_models",
                    tuple(TRANSFER_MODELS[n](self) for n in cands))

    @classmethod
    def from_columns(cls, base: ModelParams, n: int, columns,
                     cat=None) -> "_ParamArrays":
        """A view over ``n`` scenarios from COLUMN ARRAYS instead of ``n``
        ``ModelParams`` instances — the million-scenario constructor
        (:class:`~repro.core.adaptive.ArraySet` uses it).

        Varied numeric fields come from ``columns`` (``{field: (n,)
        array}``) as ``(n, 1)``; every other field broadcasts from
        ``base`` as ``(1, 1)``.  ``cat`` maps a categorical axis to
        ``(codes, choices)`` — an ``(n,)`` integer column into the static
        ``choices`` tuple — so a swept transfer-model axis never needs
        ``n`` name strings.  ``mem_lat_ns`` is always materialized at full
        length — it is the view's scenario-count carrier (``_slice`` /
        ``_pad`` / the vmap axis detection all read it).
        """
        self = object.__new__(cls)
        for f in dataclasses.fields(ModelParams):
            v = getattr(base, f.name)
            if f.name in columns:
                col = np.asarray(columns[f.name], dtype=np.float64)
                setattr(self, f.name, col.reshape(n, 1))
            elif isinstance(v, Thresholds):
                setattr(self, f.name, _ThresholdView(
                    np.array([[v.lower]], dtype=np.float64),
                    np.array([[v.upper]], dtype=np.float64)))
            else:
                setattr(self, f.name, np.array([[v]], dtype=np.float64))
        if self.mem_lat_ns.shape[0] != n:
            self.mem_lat_ns = np.full((n, 1), float(base.mem_lat_ns))
        cat = cat or {}
        for axis, default in CATEGORICAL_AXES.items():
            if axis in cat:
                codes, choices = cat[axis]
                code = np.asarray(codes, dtype=np.int32).reshape(n, 1)
                choices = tuple(choices)
            else:
                code, choices = np.zeros((1, 1), dtype=np.int32), (default,)
            setattr(self, axis + "_code", code)
            setattr(self, axis + "_models",
                    tuple(TRANSFER_MODELS[nm](self) for nm in choices))
        return self

    # -- scenario-axis slicing / padding (chunked + sharded executors) -------
    def _slice(self, sl: slice) -> "_ParamArrays":
        n = len(self.mem_lat_ns)
        out = object.__new__(_ParamArrays)
        out.__dict__.update(
            {k: _slice_val(v, sl, n) for k, v in self.__dict__.items()})
        return out

    def _pad(self, n_pad: int) -> "_ParamArrays":
        """Edge-pad every full-length leaf up to ``n_pad`` scenarios (the
        uneven-shard path of the distributed executor: the padded rows are
        physically-plausible copies of the last scenario, masked out of
        every reduction by the caller's validity mask)."""
        n = len(self.mem_lat_ns)
        if n_pad <= n:
            return self
        if n == 0:
            raise ValueError("cannot pad an empty view (0 scenarios)")
        out = object.__new__(_ParamArrays)
        out.__dict__.update(
            {k: _pad_val(v, n_pad, n) for k, v in self.__dict__.items()})
        return out


def _slice_val(val, sl, n_scenarios):
    """Recursively slice the scenario axis out of a view component: arrays
    with a leading scenario dim, threshold views, candidate-model tuples,
    and transfer models whose fields are ``(S, 1)`` arrays.  Scalars (e.g.
    an explicit override model with float fields) pass through."""
    if isinstance(val, np.ndarray):
        return val[sl] if val.ndim >= 1 and val.shape[0] == n_scenarios \
            else val
    if isinstance(val, _ThresholdView):
        return _ThresholdView(_slice_val(val.lower, sl, n_scenarios),
                              _slice_val(val.upper, sl, n_scenarios))
    if isinstance(val, tuple):
        return tuple(_slice_val(v, sl, n_scenarios) for v in val)
    if dataclasses.is_dataclass(val) and not isinstance(val, type):
        return dataclasses.replace(val, **{
            f.name: _slice_val(getattr(val, f.name), sl, n_scenarios)
            for f in dataclasses.fields(val)})
    return val


def _pad_val(val, n_pad, n_scenarios):
    """The ``_pad`` counterpart of :func:`_slice_val`: edge-pad arrays
    carrying the scenario axis, recurse into the same containers, pass
    everything else through."""
    if isinstance(val, np.ndarray):
        if val.ndim >= 1 and val.shape[0] == n_scenarios:
            from ..compat import pad_to_multiple
            return pad_to_multiple(val, n_pad, axis=0)
        return val
    if isinstance(val, _ThresholdView):
        return _ThresholdView(_pad_val(val.lower, n_pad, n_scenarios),
                              _pad_val(val.upper, n_pad, n_scenarios))
    if isinstance(val, tuple):
        return tuple(_pad_val(v, n_pad, n_scenarios) for v in val)
    if dataclasses.is_dataclass(val) and not isinstance(val, type):
        return dataclasses.replace(val, **{
            f.name: _pad_val(getattr(val, f.name), n_pad, n_scenarios)
            for f in dataclasses.fields(val)})
    return val


@runtime_checkable
class ScenarioSet(Protocol):
    """What the pricing engine needs from a scenario source.

    :class:`ParamGrid` is the canonical implementation (product, sampled,
    zipped and concatenated constructors all return one), but any object
    exposing these members — a streaming scenario generator, an
    adaptively-refined design, ... — prices through
    :func:`repro.core.price` unchanged:

      * ``__len__()`` — the scenario count ``S``;
      * ``view()`` — the ``(S, 1)``-array parameter view the kernels
        consume (see ``_ParamArrays``; must support ``._slice`` for
        ``ExecPlan.chunk_scenarios``);
      * ``labels()`` — one dict per scenario naming the varied axes
        (feeds ``SweepResult.summary_rows``).
    """

    def __len__(self) -> int: ...

    def view(self): ...

    def labels(self) -> list: ...


def _axis_values(name: str, vals, valid) -> list:
    """Normalize + validate one grid-axis value list (shared by the
    ParamGrid constructors): unknown fields and EMPTY axes raise
    immediately — an empty axis would silently yield a 0-scenario grid."""
    if name not in valid and name not in CATEGORICAL_AXES:
        raise ValueError(f"unknown ModelParams field: {name!r}")
    vals = list(vals)
    if not vals:
        raise ValueError(f"empty axis {name!r}: it would yield a "
                         "0-scenario grid; drop the axis or give it values")
    if name in CATEGORICAL_AXES:
        for v in vals:
            if v not in TRANSFER_MODELS:
                raise ValueError(
                    f"unknown transfer model {v!r} for axis {name!r}; "
                    f"known: {sorted(TRANSFER_MODELS)}")
    return vals


@dataclass(frozen=True)
class ParamGrid:
    """An ordered collection of scenarios (``ModelParams`` points) — the
    canonical :class:`ScenarioSet`.

    ``axes`` records the varied fields when built via :meth:`product`
    (useful for reshaping a sweep row back into grid form); ``cat`` holds
    the per-scenario assignment of each categorical axis; ``rows`` holds
    explicit per-scenario labels for the non-factorial constructors
    (:meth:`sample` / :meth:`zip` / :meth:`concat`).
    """

    params: tuple
    axes: tuple = ()          # ((axis_name, (values...)), ...)
    cat: tuple = ()           # ((axis_name, (per-scenario name, ...)), ...)
    rows: tuple = ()          # per-scenario ((axis_name, value), ...) pairs
    ranges: tuple = ()        # ((axis, (lo, hi) | (choices...)), ...) from
    #                           sample() — what refine() re-samples within

    @staticmethod
    def from_params(params) -> "ParamGrid":
        return ParamGrid(params=tuple(params))

    @staticmethod
    def product(base: ModelParams | None = None, **axes) -> "ParamGrid":
        """Cartesian grid over ``ModelParams`` fields and the categorical
        transfer-model axes, e.g.  ``ParamGrid.product(base,
        cxl_lat_ns=[...], mpi_transfer=["hockney", "loggp"])``.
        Later axes vary fastest (C order), so a sweep row reshapes to
        ``tuple(len(v) for v in axes.values())``."""
        base = base or ModelParams()
        valid = {f.name for f in dataclasses.fields(ModelParams)}
        cols = {n: _axis_values(n, v, valid) for n, v in axes.items()}
        cat_names = [n for n in cols if n in CATEGORICAL_AXES]
        points, cat_cols = [], {n: [] for n in cat_names}
        for combo in itertools.product(*cols.values()):
            d = dict(zip(cols, combo))
            for n in cat_names:
                cat_cols[n].append(d.pop(n))
            points.append(base.replace(**d))
        return ParamGrid(params=tuple(points),
                         axes=tuple((n, tuple(v)) for n, v in cols.items()),
                         cat=tuple((n, tuple(cat_cols[n]))
                                   for n in cat_names))

    @staticmethod
    def sample(base: ModelParams | None = None, n: int = 16, *,
               seed: int = 0, method: str = "lhs",
               **ranges) -> "ParamGrid":
        """``n`` scenarios sampled from axis RANGES instead of a factorial
        grid — the non-factorial exploration the CXL measurement studies
        motivate (interesting design points are scattered, not gridded).

        Numeric axes take a ``(lo, hi)`` pair; categorical transfer-model
        axes take a list of model names.  ``method="lhs"`` (default)
        stratifies each axis Latin-hypercube style — every axis gets one
        sample per ``1/n`` stratum (categoricals cycle near-evenly) —
        while ``method="uniform"`` draws i.i.d.  Deterministic per
        ``seed``.

            ParamGrid.sample(ModelParams.multinode(), 64, seed=1,
                             cxl_lat_ns=(250, 700),
                             cxl_atomic_lat_ns=(300, 800),
                             mpi_transfer=["hockney", "loggp"])
        """
        base = base or ModelParams()
        if n < 1:
            raise ValueError(f"sample needs n >= 1, got {n}")
        if method not in ("lhs", "uniform"):
            raise ValueError(f"unknown sample method {method!r}; "
                             "use 'lhs' or 'uniform'")
        if not ranges:
            raise ValueError("sample needs at least one axis range")
        valid = {f.name for f in dataclasses.fields(ModelParams)}
        rng = np.random.default_rng(seed)
        num_cols, cat_cols = {}, {}
        for name, spec in ranges.items():
            vals = _axis_values(name, spec, valid)
            if name in CATEGORICAL_AXES:
                if method == "lhs":     # near-even coverage, then shuffled
                    idx = np.tile(np.arange(len(vals)),
                                  -(-n // len(vals)))[:n]
                    rng.shuffle(idx)
                else:
                    idx = rng.integers(0, len(vals), size=n)
                cat_cols[name] = [vals[int(k)] for k in idx]
                continue
            if len(vals) != 2:
                raise ValueError(f"axis {name!r}: numeric sample ranges "
                                 f"are (lo, hi) pairs, got {spec!r}")
            lo, hi = float(vals[0]), float(vals[1])
            if not hi >= lo:
                raise ValueError(f"axis {name!r}: lo ({lo}) must not "
                                 f"exceed hi ({hi})")
            if method == "lhs":         # one draw per 1/n stratum, permuted
                u = (rng.permutation(n) + rng.uniform(size=n)) / n
            else:
                u = rng.uniform(size=n)
            num_cols[name] = lo + u * (hi - lo)
        points, rows = [], []
        for i in range(n):
            d = {k: float(col[i]) for k, col in num_cols.items()}
            points.append(base.replace(**d))
            lab = dict(d)
            lab.update({k: col[i] for k, col in cat_cols.items()})
            rows.append(tuple(lab.items()))
        recorded = tuple(
            (name, (float(spec[0]), float(spec[1]))
             if name not in CATEGORICAL_AXES else tuple(spec))
            for name, spec in ranges.items())
        return ParamGrid(params=tuple(points),
                         cat=tuple((k, tuple(col))
                                   for k, col in cat_cols.items()),
                         rows=tuple(rows), ranges=recorded)

    @staticmethod
    def zip(base: ModelParams | None = None, **axes) -> "ParamGrid":
        """PAIRED axes: scenario ``i`` takes element ``i`` of every axis
        (all axes must share one length) — calibrated design points that
        move together, e.g. measured (latency, atomic-latency) pairs,
        without the factorial cross ``product`` would take."""
        base = base or ModelParams()
        if not axes:
            raise ValueError("zip needs at least one axis")
        valid = {f.name for f in dataclasses.fields(ModelParams)}
        cols = {n: _axis_values(n, v, valid) for n, v in axes.items()}
        lengths = {n: len(v) for n, v in cols.items()}
        if len(set(lengths.values())) > 1:
            raise ValueError(f"zip axes must share one length, got "
                             f"{lengths}")
        length = next(iter(lengths.values()))
        cat_names = [n for n in cols if n in CATEGORICAL_AXES]
        points, rows = [], []
        for i in range(length):
            d = {n: cols[n][i] for n in cols}
            lab = dict(d)
            for cn in cat_names:
                d.pop(cn)
            points.append(base.replace(**d))
            rows.append(tuple(lab.items()))
        return ParamGrid(params=tuple(points),
                         cat=tuple((cn, tuple(cols[cn]))
                                   for cn in cat_names),
                         rows=tuple(rows))

    @staticmethod
    def concat(*grids) -> "ParamGrid":
        """Union of scenario sets: the grids' scenarios back-to-back, in
        order.  Categorical-axis aware — if any grid sweeps a transfer-
        model axis, grids that don't are filled with that axis's default
        (``CATEGORICAL_AXES``), so mixed unions price correctly.  Labels
        concatenate each grid's own ``labels()``."""
        if len(grids) == 1 and not isinstance(grids[0], ParamGrid):
            grids = tuple(grids[0])             # concat(iterable_of_grids)
        if not grids:
            raise ValueError("concat needs at least one grid")
        cat_names = []
        for g in grids:
            for name, _ in g.cat:
                if name not in cat_names:
                    cat_names.append(name)
        cat = []
        for name in cat_names:
            col = []
            for g in grids:
                per = dict(g.cat).get(name)
                col.extend(per if per is not None
                           else (CATEGORICAL_AXES[name],) * len(g))
            cat.append((name, tuple(col)))
        rows = []
        for g in grids:
            # a grid that doesn't sweep a union categorical axis is priced
            # under that axis's default — say so in its labels too
            filled = {name: CATEGORICAL_AXES[name] for name in cat_names
                      if name not in dict(g.cat)}
            rows.extend(tuple({**filled, **lab}.items())
                        for lab in g.labels())
        rows = tuple(rows)
        return ParamGrid(params=tuple(p for g in grids for p in g.params),
                         cat=tuple(cat), rows=rows)

    @property
    def shape(self) -> tuple:
        return tuple(len(v) for _, v in self.axes) if self.axes \
            else (len(self.params),)

    def labels(self) -> list:
        """Per-scenario dict of the varied axes — numeric fields AND
        categorical transfer-model names (empty dicts for a bare
        ``from_params`` collection)."""
        if self.rows:
            return [dict(r) for r in self.rows]
        if not self.axes:
            return [{} for _ in self.params]
        names = [n for n, _ in self.axes]
        return [dict(zip(names, combo)) for combo in
                itertools.product(*(v for _, v in self.axes))]

    def label_at(self, i: int) -> dict:
        """``labels()[i]`` without materializing all ``S`` label dicts
        (what the adaptive refiner reads for its frontier points)."""
        if self.rows:
            return dict(self.rows[i])
        if not self.axes:
            return {}
        names = [n for n, _ in self.axes]
        vals, rem = [], int(i)
        for _, axis_vals in reversed(self.axes):     # later axes fastest
            rem, j = divmod(rem, len(axis_vals))
            vals.append(axis_vals[j])
        return dict(zip(names, reversed(vals)))

    def subset(self, indices) -> "ParamGrid":
        """The scenarios at ``indices``, in that order, as a new grid
        (labels preserved; the factorial ``axes`` structure does not
        survive an arbitrary selection, so the result is row-labeled)."""
        idx = [int(i) for i in np.asarray(indices).ravel()]
        return ParamGrid(
            params=tuple(self.params[i] for i in idx),
            cat=tuple((name, tuple(col[i] for i in idx))
                      for name, col in self.cat),
            rows=tuple(tuple(self.label_at(i).items()) for i in idx),
            ranges=self.ranges)

    def refine(self, points, n: int, *, seed: int = 0,
               shrink: float = 0.25):
        """``n`` new scenarios re-sampled around ``points`` (label dicts,
        e.g. ``[grid.label_at(i) for i in frontier]``) within the ranges
        recorded by :meth:`sample` — each numeric axis draws uniformly
        from a ``shrink``-scaled neighborhood of its center, clamped to
        the original range; categorical axes keep the center's choice.
        Returns an array-backed :class:`~repro.core.adaptive.ArraySet`
        (a :class:`ScenarioSet`; concat-able with the seed's own
        ``ArraySet`` form)."""
        from .adaptive import as_array_set
        return as_array_set(self).refine(points, n, seed=seed,
                                         shrink=shrink)

    def view(self) -> _ParamArrays:
        return _ParamArrays(self.params, dict(self.cat))

    def __len__(self) -> int:
        return len(self.params)


# --------------------------------------------------------------------------
# Bundle compilation: TraceBundle -> packed flat arrays
# --------------------------------------------------------------------------

def _pack_group(per_site_lat, per_site_w):
    """Concatenate per-site sample vectors; return (lat, w, starts, counts)."""
    counts = np.array([len(v) for v in per_site_lat], dtype=np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]]) if len(counts) \
        else np.zeros(0, np.int64)
    lat = np.concatenate(per_site_lat) if per_site_lat else np.zeros(0)
    w = np.concatenate(per_site_w) if per_site_w else np.zeros(0)
    return lat, w, starts.astype(np.int64), counts


@dataclass(frozen=True)
class CompiledBundle:
    """A ``TraceBundle`` lowered to flat arrays, scenario-independent parts
    pre-reduced.  Compile once, sweep many.

    Each packed sample group carries BOTH segmentation encodings: starts /
    counts for the reduceat-based NumPy backend and per-sample segment ids
    (``*_seg``) for scatter-style backends (``jax.ops.segment_sum`` today,
    the planned Pallas kernel next).
    """

    call_ids: tuple
    # packed per-source-class samples (site-major, original order kept)
    hit_lat: np.ndarray; hit_w: np.ndarray
    hit_starts: np.ndarray; hit_counts: np.ndarray; hit_seg: np.ndarray
    lfb_lat: np.ndarray; lfb_w: np.ndarray
    lfb_starts: np.ndarray; lfb_counts: np.ndarray; lfb_seg: np.ndarray
    miss_lat: np.ndarray; miss_w: np.ndarray
    miss_starts: np.ndarray; miss_counts: np.ndarray; miss_seg: np.ndarray
    # scenario-independent per-site reductions, all shape (n_calls,)
    hit_wl_sum: np.ndarray      # Σ w·lat over cache hits
    lfb_wl_sum: np.ndarray      # Σ w·lat over LFB
    miss_w_sum: np.ndarray      # Σ w over DRAM misses
    total_wl: np.ndarray        # Σ w·lat over ALL samples (Eq. 5)
    # per-site comm aggregates / metadata
    traffic: SiteTraffic        # fields are (n_calls,) arrays
    buffer_bytes: np.ndarray
    accesses_per_element: np.ndarray
    prefetch_frac: np.ndarray
    unpack: np.ndarray          # bool
    counters: object            # CounterSet (whole-run, scenario-independent)
    sampling_period: float
    baseline_runtime_ns: float

    @property
    def n_calls(self) -> int:
        return len(self.call_ids)

    def padded_groups(self, multiple: int = 128) -> dict:
        """The packed sample groups in the pallas-friendly padded layout:
        ``{"hit" | "lfb" | "miss": (lat, w, seg)}`` where all three share
        ONE zero-padded length (a multiple of ``multiple`` — the TPU lane
        width by default), so a kernel can tile the three sample axes with
        a single grid.  Padding rows carry ``w == 0`` (they contribute
        exactly zero to every bracket) and ``seg == 0`` (always a valid
        id).  Cached on the bundle per ``multiple``.
        """
        cache = getattr(self, "_padded_groups", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_padded_groups", cache)
        out = cache.get(multiple)
        if out is None:
            n = max(len(self.hit_lat), len(self.lfb_lat),
                    len(self.miss_lat), 1)
            n_pad = -(-n // multiple) * multiple

            def pad(grp):
                lat = getattr(self, grp + "_lat")
                w = getattr(self, grp + "_w")
                seg = getattr(self, grp + "_seg")
                k = n_pad - len(lat)
                return (np.pad(lat, (0, k)), np.pad(w, (0, k)),
                        np.pad(seg, (0, k)).astype(np.int32))

            out = {grp: pad(grp) for grp in ("hit", "lfb", "miss")}
            cache[multiple] = out
        return out


def compile_bundle(bundle: TraceBundle) -> CompiledBundle:
    """Lower a bundle to packed arrays (site order = dict insertion order,
    matching ``predict_run``)."""
    call_ids, groups = [], {"hit": ([], []), "lfb": ([], []), "miss": ([], [])}
    hit_wl, lfb_wl, miss_w, total_wl = [], [], [], []
    n_msgs, total_bytes, gap_bytes, buffer_bytes = [], [], [], []
    ape, pf, unpack = [], [], []

    for cid, site in bundle.call_sites.items():
        call_ids.append(cid)
        a = SampleArrays.of(site.samples)
        for key, mask in (("hit", a.is_hit), ("lfb", a.is_lfb),
                          ("miss", a.is_miss)):
            groups[key][0].append(a.lat[mask])
            groups[key][1].append(a.weight[mask])
        hit_wl.append(float(np.sum(a.weight[a.is_hit] * a.lat[a.is_hit])))
        lfb_wl.append(float(np.sum(a.weight[a.is_lfb] * a.lat[a.is_lfb])))
        miss_w.append(float(np.sum(a.weight[a.is_miss])))
        total_wl.append(float(np.sum(a.weight * a.lat)))
        t = SiteTraffic.of(site)
        n_msgs.append(t.n_msgs)
        total_bytes.append(t.total_bytes)
        gap_bytes.append(t.gap_bytes)
        buffer_bytes.append(max((c.bytes for c in site.comms), default=0))
        ape.append(site.accesses_per_element)
        pf.append(prefetch_hit_fraction(site))
        unpack.append(bool(site.unpack))

    h = _pack_group(*groups["hit"])
    l = _pack_group(*groups["lfb"])
    m = _pack_group(*groups["miss"])
    seg = lambda counts: np.repeat(np.arange(len(counts), dtype=np.int32),
                                   counts)
    arr = lambda v, dt=np.float64: np.asarray(v, dtype=dt)
    return CompiledBundle(
        call_ids=tuple(call_ids),
        hit_lat=h[0], hit_w=h[1], hit_starts=h[2], hit_counts=h[3],
        hit_seg=seg(h[3]),
        lfb_lat=l[0], lfb_w=l[1], lfb_starts=l[2], lfb_counts=l[3],
        lfb_seg=seg(l[3]),
        miss_lat=m[0], miss_w=m[1], miss_starts=m[2], miss_counts=m[3],
        miss_seg=seg(m[3]),
        hit_wl_sum=arr(hit_wl), lfb_wl_sum=arr(lfb_wl),
        miss_w_sum=arr(miss_w), total_wl=arr(total_wl),
        traffic=SiteTraffic(n_msgs=arr(n_msgs), total_bytes=arr(total_bytes),
                            gap_bytes=arr(gap_bytes)),
        buffer_bytes=arr(buffer_bytes),
        accesses_per_element=arr(ape), prefetch_frac=arr(pf),
        unpack=np.asarray(unpack, dtype=bool),
        # counters recorded as Python ints (memsim sums load counts) would
        # enter a float32 jit as int32 and overflow past 2**31
        counters=dataclasses.replace(bundle.counters, **{
            f.name: float(getattr(bundle.counters, f.name))
            for f in dataclasses.fields(bundle.counters)}),
        sampling_period=float(bundle.sampling_period),
        baseline_runtime_ns=float(bundle.counters.wall_time_ns))


# --------------------------------------------------------------------------
# The sweep
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class SweepResult:
    """``(n_scenarios, n_calls)`` component matrices + per-scenario views.

    Mirrors ``RunPrediction``'s three paper questions, batched:
      1. per-call verdicts        -> :attr:`gain_ns` / :meth:`beneficial_mask`
      2. where to invest first    -> :meth:`ranked_call_indices`
      3. limited CXL capacity     -> :meth:`prioritize_for_capacity`
    plus the application-level projection (:meth:`predicted_speedup`).

    The four matrices are writable float64 ``(n_scenarios, n_calls)``
    arrays.  An unchunked sweep hands out the transposed, F-ordered view
    of one call-major block per matrix; a chunked one C-ordered arrays.
    No two results of one sweep share memory.
    """

    grid: ParamGrid
    compiled: CompiledBundle
    t_transfer_mpi_ns: np.ndarray
    t_transfer_cxl_ns: np.ndarray
    t_access_mpi_ns: np.ndarray
    t_access_cxl_ns: np.ndarray
    #: the resolved :class:`ExecPlan` the matrices were priced under
    plan: ExecPlan | None = None

    # -- per-call matrices ---------------------------------------------------
    @property
    def call_ids(self) -> tuple:
        return self.compiled.call_ids

    @property
    def t_mpi_ns(self) -> np.ndarray:
        return self.t_transfer_mpi_ns + self.t_access_mpi_ns

    @property
    def t_cxl_ns(self) -> np.ndarray:
        return self.t_transfer_cxl_ns + self.t_access_cxl_ns

    @property
    def gain_ns(self) -> np.ndarray:
        """Positive = switching this call to message-free saves time."""
        return self.t_mpi_ns - self.t_cxl_ns

    @property
    def speedup(self) -> np.ndarray:
        """Per-call ``t_mpi / t_cxl``.  A zero-traffic call (both times 0)
        is a no-op, not an infinite win — it reports 1.0; ``t_cxl == 0 <
        t_mpi`` still reports ``inf``."""
        t_cxl, t_mpi = self.t_cxl_ns, self.t_mpi_ns
        return np.where(t_cxl > 0, t_mpi / np.where(t_cxl > 0, t_cxl, 1.0),
                        np.where(t_mpi > 0, np.inf, 1.0))

    def beneficial_mask(self) -> np.ndarray:
        return self.gain_ns > 0

    def n_beneficial(self) -> np.ndarray:
        return self.beneficial_mask().sum(axis=1)

    def ranked_call_indices(self) -> np.ndarray:
        """Per scenario, call indices sorted by descending gain (question 2)."""
        return np.argsort(-self.gain_ns, axis=1, kind="stable")

    # -- question 3: limited CXL capacity ------------------------------------
    def prioritize_for_capacity(self, capacity_bytes: int):
        """Greedy gain-per-byte knapsack per scenario (same semantics as
        ``RunPrediction.prioritize_for_capacity``: an over-budget buffer is
        skipped, later smaller ones may still fit).

        Returns ``(chosen (S, C) bool, used_bytes (S,))``.
        """
        gain = self.gain_ns
        buf = self.compiled.buffer_bytes
        gpb = gain / np.maximum(1, buf)
        S, C = gain.shape
        order = np.argsort(-gpb, axis=1, kind="stable")
        rows = np.arange(S)
        chosen = np.zeros((S, C), dtype=bool)
        used = np.zeros(S, dtype=np.float64)
        for j in range(C):
            idx = order[:, j]
            fits = (gain[rows, idx] > 0) & (used + buf[idx] <= capacity_bytes)
            chosen[rows, idx] |= fits
            used = used + np.where(fits, buf[idx], 0.0)
        return chosen, used

    # -- application-level projection ----------------------------------------
    def _selection(self, replaced=None) -> np.ndarray:
        if replaced is None:
            return np.ones(self.compiled.n_calls, dtype=bool)
        replaced = set(replaced)
        return np.array([cid in replaced for cid in self.call_ids], dtype=bool)

    def predicted_runtime_ns(self, replaced=None) -> np.ndarray:
        """(S,) baseline wall time with the selected calls swapped."""
        sel = self._selection(replaced)
        return self.compiled.baseline_runtime_ns \
            - (self.gain_ns * sel).sum(axis=1)

    def predicted_speedup(self, replaced=None) -> np.ndarray:
        """(S,) application-level speedup per scenario (empty ``(0,)``
        array for an empty grid — there is nothing to project)."""
        return self.compiled.baseline_runtime_ns \
            / self.predicted_runtime_ns(replaced)

    def best_scenario(self, replaced=None) -> int:
        if len(self.grid) == 0:
            raise ValueError("best_scenario() on an empty grid: the sweep "
                             "has 0 scenarios, so there is no argmax")
        return int(np.argmax(self.predicted_speedup(replaced)))

    def topk(self, k: int, replaced=None) -> np.ndarray:
        """Indices of the ``min(k, S)`` best scenarios by predicted
        speedup, best first, ties broken toward the LOWER index — exactly
        the order the streaming distributed reducer produces, so matrix
        and streaming sweeps can be compared row for row."""
        sp = self.predicted_speedup(replaced)
        order = np.lexsort((np.arange(len(sp)), -sp))
        return order[:min(int(k), len(sp))]

    # -- parity / inspection helpers ----------------------------------------
    def scenario_calls(self, i: int) -> dict:
        """Row ``i`` as ``call_id -> CallPrediction`` (scalar-path parity)."""
        cb = self.compiled
        out = {}
        for j, cid in enumerate(cb.call_ids):
            out[cid] = CallPrediction(
                call_id=cid,
                t_transfer_mpi_ns=float(self.t_transfer_mpi_ns[i, j]),
                t_transfer_cxl_ns=float(self.t_transfer_cxl_ns[i, j]),
                t_access_mpi_ns=float(self.t_access_mpi_ns[i, j]),
                t_access_cxl_ns=float(self.t_access_cxl_ns[i, j]),
                transfer_bytes=int(cb.traffic.total_bytes[j]),
                buffer_bytes=int(cb.buffer_bytes[j]))
        return out

    def summary_rows(self, replaced=None) -> list:
        """One dict per scenario: varied params (numeric AND categorical
        transfer-model axes) + aggregates."""
        speed = self.predicted_speedup(replaced)
        nben = self.n_beneficial()
        gain = np.maximum(0.0, self.gain_ns).sum(axis=1)
        rows = []
        for i, lab in enumerate(self.grid.labels()):
            rows.append({**lab,
                         "predicted_speedup": float(speed[i]),
                         "n_beneficial": int(nben[i]),
                         "total_positive_gain_us": float(gain[i]) / 1e3})
        return rows


@dataclass(frozen=True)
class SweepAggregates:
    """Exact whole-sweep reductions a streaming backend reports instead of
    the full ``(S, n_calls)`` matrices (and :meth:`from_result` computes
    from a matrix :class:`SweepResult` — the parity reference).

    ``hist`` buckets predicted speedups by
    ``searchsorted(SPEEDUP_HIST_EDGES, sp, side="right")`` —
    ``len(edges) + 1`` bins including underflow and overflow.
    ``n_beneficial`` / ``gain_sum`` are PER-CALL: in how many scenarios
    call ``j`` gains, and its summed gain over all scenarios.
    """

    count: int
    speedup_mean: float
    speedup_min: float
    speedup_max: float
    hist: np.ndarray
    n_beneficial: np.ndarray
    gain_sum: np.ndarray

    @staticmethod
    def from_result(res: SweepResult, replaced=None) -> "SweepAggregates":
        sp = res.predicted_speedup(replaced)
        hist = np.bincount(
            np.searchsorted(SPEEDUP_HIST_EDGES, sp, side="right"),
            minlength=len(SPEEDUP_HIST_EDGES) + 1).astype(np.int64)
        gain = res.gain_ns
        return SweepAggregates(
            count=len(sp),
            speedup_mean=float(sp.mean()) if len(sp) else 0.0,
            speedup_min=float(sp.min()) if len(sp) else np.inf,
            speedup_max=float(sp.max()) if len(sp) else -np.inf,
            hist=hist,
            n_beneficial=(gain > 0).sum(axis=0).astype(np.int64),
            gain_sum=gain.sum(axis=0, dtype=np.float64))


@dataclass(frozen=True)
class TopKSweepResult:
    """What a STREAMING sweep returns: the ``k`` best scenarios with full
    per-call detail, plus exact whole-sweep aggregates — never the
    ``(S, n_calls)`` matrices.

    ``indices`` are global scenario indices into ``scenarios`` (the full
    set evaluated, INCLUDING adaptively-refined rounds), best speedup
    first with ties toward the lower index — the same order
    ``SweepResult.topk`` yields.  ``result`` is an exact matrix-backend
    re-evaluation of exactly those scenarios (``result.grid ==
    scenarios.subset(indices)``), so every ``SweepResult`` question —
    per-call gains, capacity knapsack, summary rows — is answerable for
    the survivors.  ``shard_rows`` is the peak per-device scenario-row
    allocation the streaming pass needed (the memory bound tests assert).
    """

    scenarios: object
    indices: np.ndarray
    speedups: np.ndarray
    result: SweepResult
    aggregates: SweepAggregates
    plan: object
    shard_rows: int

    def __len__(self) -> int:
        return len(self.indices)

    def labels(self) -> list:
        """Varied-axis labels of the surviving scenarios, best first."""
        return self.result.grid.labels()

    def summary_rows(self, replaced=None) -> list:
        return self.result.summary_rows(replaced)

    def best_scenario(self) -> int:
        """Global index of the best scenario in :attr:`scenarios`."""
        if len(self.indices) == 0:
            raise ValueError("best_scenario() on an empty sweep")
        return int(self.indices[0])


def _chunk_slices(n: int, chunk: int):
    for lo in range(0, n, chunk):
        yield slice(lo, min(lo + chunk, n))


def _scenario_view(grid, mpi_transfer=None, free_transfer=None):
    """Build the kernel view for a :class:`ScenarioSet` with the explicit
    transfer-model overrides applied — shared by the matrix execution core
    and the streaming executors (which chunk/shard the returned view
    themselves)."""
    v = grid.view()
    S = len(grid)
    swept = dict(getattr(grid, "cat", ()) or ())
    for side, model in (("mpi_transfer", mpi_transfer),
                        ("free_transfer", free_transfer)):
        if model is None:
            continue
        if side in swept:
            raise ValueError(
                f"{side} is both a categorical grid axis and an explicit "
                f"transfer-model override; use one or the other")
        setattr(v, side + "_models", (model,))
        setattr(v, side + "_code", np.zeros((S, 1), dtype=np.int32))
    return v


def _sweep_plan(cb: CompiledBundle, grid, plan: ExecPlan | None,
                mpi_transfer=None, free_transfer=None):
    """The execution core behind ``price()``: one compiled bundle, one
    :class:`ScenarioSet`, one :class:`ExecPlan`.

    The backend comes from the ``execplan`` registry (unknown names raise
    the canonical usage error).  A MATRIX backend returns a full
    :class:`SweepResult`; scenario-axis chunking wraps any of them with
    bit-identical results (every scenario row is computed independently).
    A STREAMING backend (``is_streaming``) owns its whole execution —
    chunking, sharding, reduction — and returns its own result type
    (canonically :class:`TopKSweepResult`).
    """
    plan = (plan if plan is not None else ExecPlan()).resolved()
    if is_streaming(plan.backend):
        return resolve_backend(plan.backend)(cb, grid, plan, mpi_transfer,
                                             free_transfer)
    (mats,) = _price_matrices(cb, grid, plan, [cb.n_calls],
                              mpi_transfer, free_transfer)
    return SweepResult(grid=grid, compiled=cb, plan=plan, **mats)


def _price_matrices(cb: CompiledBundle, grid, plan: ExecPlan, widths,
                    mpi_transfer=None, free_transfer=None) -> list:
    """Price ``cb`` under ``grid`` with ``plan``'s (resolved) matrix
    backend and return one ``{field: (S, w) float64 matrix}`` per entry of
    ``widths``, the call counts of consecutive column ranges (one per
    bundle of a super-bundle; they sum to ``cb.n_calls``).

    Unchunked, each range's matrix is the transposed view of a fresh
    call-major ``(w, S)`` block (F-ordered); chunked, it is preallocated
    C-ordered and each chunk writes its rows.  Either way every matrix is
    writable and shares memory with no other.
    """
    run = resolve_backend(plan.backend)
    S = len(grid)
    bounds = list(zip(np.cumsum([0, *widths[:-1]]), np.cumsum(widths)))
    if S == 0 or cb.n_calls == 0:
        return [{f: np.zeros((S, hi - lo)) for f in MATRIX_FIELDS}
                for lo, hi in bounds]
    v = _scenario_view(grid, mpi_transfer, free_transfer)
    chunk = plan.chunk_scenarios
    if chunk is None:
        blocks = [{f: np.empty((hi - lo, S)) for f in MATRIX_FIELDS}
                  for lo, hi in bounds]
        _assemble(run(cb, v, plan), bounds, blocks)
        return [{f: b.T for f, b in blk.items()} for blk in blocks]
    # a plan that chunks keeps C-ordered matrices, even in one chunk:
    # preallocate them ONCE and write each chunk's rows in place —
    # concatenating per-chunk copies cost ~2.5x at small chunk sizes
    mats = [{f: np.empty((S, hi - lo)) for f in MATRIX_FIELDS}
            for lo, hi in bounds]
    for sl in _chunk_slices(S, chunk):
        _assemble(run(cb, v._slice(sl), plan), bounds,
                  [{f: m[sl].T for f, m in ms.items()} for ms in mats])
    return mats


def _call_major(a):
    """A matrix broadcastable to ``(s, c)`` as a 2-D ``(c | 1, s | 1)``
    transpose: a view of a host array, or one op on a device array, whose
    copy to the host it starts."""
    device = hasattr(a, "copy_to_host_async")
    a = a if device else np.asarray(a)
    a = a.reshape((1,) * (2 - a.ndim) + a.shape).T
    if device:
        a.copy_to_host_async()
    return a


def _assemble(out: dict, bounds, blocks: list) -> None:
    """Write one executor output into float64 call-major blocks: for each
    field and each call range ``[lo, hi)`` of ``bounds``, the field's rows
    ``lo:hi`` of its call-major form go into ``blocks[i][field]``, a
    writable ``(hi - lo, s)`` array, in ONE assignment that widens to
    float64 and broadcasts a field with no scenario axis.

    Executor outputs are merely broadcastable to ``(s, n_calls)``; device
    outputs are transposed on the device, and every copy to the host is
    started before the first is waited on.  The ``repro.price.fetch``
    span's ``host_mb`` is the float64 megabytes written.
    """
    host_mb = sum(b.size for blk in blocks for b in blk.values()) * 8 / 1e6
    with span("repro.price.fetch", host_mb=host_mb):
        cm = {f: _call_major(out[f]) for f in MATRIX_FIELDS}
        for f, a in cm.items():
            a = np.asarray(a)
            for (lo, hi), blk in zip(bounds, blocks):
                blk[f][...] = a if a.shape[0] == 1 else a[lo:hi]


def sweep_run(bundle, grid: ParamGrid, mpi_transfer=None, free_transfer=None,
              backend=_UNSET, chunk_scenarios=_UNSET, vmap_scenarios=_UNSET,
              pallas_interpret=_UNSET, plan: ExecPlan | None = None
              ) -> SweepResult:
    """Evaluate every scenario of ``grid`` against one compiled bundle.

    Thin wrapper over the :func:`repro.core.price` execution core kept
    for the established call sites.  ``bundle`` may be a ``TraceBundle``
    (compiled on the fly) or an already-``compile_bundle``d
    ``CompiledBundle``.

    Execution config travels in ``plan`` (an :class:`ExecPlan`, or its
    ``"backend[:opt=val,...]"`` string form).  The per-call kwargs
    ``backend=`` / ``chunk_scenarios=`` / ``vmap_scenarios=`` /
    ``pallas_interpret=`` are DEPRECATED — they still work (mapped onto
    an equivalent ``ExecPlan``, bit-identical results) but emit one
    ``DeprecationWarning`` per call.

    ``mpi_transfer`` / ``free_transfer`` override the Hockney / two-atomic
    transfer models with an explicit model instance; their fields may be
    scalars (same for every scenario) or ``(S, 1)`` arrays (per-scenario).
    To mix transfer models WITHIN the grid, use the categorical
    ``mpi_transfer=`` / ``free_transfer=`` axes of ``ParamGrid.product``
    instead (the two mechanisms are mutually exclusive).
    """
    plan = legacy_plan(plan, "sweep_run", backend=backend,
                       chunk_scenarios=chunk_scenarios,
                       vmap_scenarios=vmap_scenarios,
                       pallas_interpret=pallas_interpret)
    cb = bundle if isinstance(bundle, CompiledBundle) else compile_bundle(bundle)
    return _sweep_plan(cb, grid, plan, mpi_transfer, free_transfer)


# --------------------------------------------------------------------------
# Multi-bundle sweeps: many compiled steps, one batched evaluation
# --------------------------------------------------------------------------

def concat_bundles(bundles) -> CompiledBundle:
    """Pack several ``CompiledBundle``s into ONE super-bundle.

    The packed sample groups are concatenated with their segment ids /
    starts offset by the running call count, so a single segment-sum pass
    prices every call-site of every bundle at once.  Per-bundle scalars
    that enter the pricing kernel — the PAPI counter set and the sampling
    period — become ``(n_calls,)`` arrays (each bundle's value repeated
    over its call-sites); the kernel's math is elementwise in those, so
    each column prices exactly as it does in a per-bundle run.

    ``baseline_runtime_ns`` of the super-bundle is the SUM of the parts
    (one execution of each step); per-bundle projections should use the
    per-bundle ``SweepResult``s that ``sweep_run_many`` unpacks.
    """
    from .traces import CounterSet

    bundles = list(bundles)
    if not bundles:
        raise ValueError("concat_bundles needs at least one bundle")
    reps = np.array([cb.n_calls for cb in bundles], dtype=np.int64)

    def rep_counter(field):
        vals = np.array([getattr(cb.counters, field) for cb in bundles],
                        dtype=np.float64)
        return np.repeat(vals, reps)

    def cat(field, dtype=None):
        parts = [getattr(cb, field) for cb in bundles]
        out = np.concatenate(parts) if parts else np.zeros(0)
        return out.astype(dtype) if dtype is not None else out

    def cat_group(grp):
        lat = cat(grp + "_lat")
        w = cat(grp + "_w")
        counts = cat(grp + "_counts", np.int64)
        samp_off = np.cumsum([0] + [len(getattr(cb, grp + "_lat"))
                                    for cb in bundles[:-1]])
        call_off = np.cumsum([0] + [cb.n_calls for cb in bundles[:-1]])
        starts = np.concatenate(
            [getattr(cb, grp + "_starts") + off
             for cb, off in zip(bundles, samp_off)]).astype(np.int64)
        seg = np.concatenate(
            [getattr(cb, grp + "_seg") + np.int32(off)
             for cb, off in zip(bundles, call_off)]).astype(np.int32)
        return lat, w, starts, counts, seg

    h, l, m = cat_group("hit"), cat_group("lfb"), cat_group("miss")
    counters = CounterSet(
        ld_ins=rep_counter("ld_ins"), l1_ldm=rep_counter("l1_ldm"),
        l3_ldm=rep_counter("l3_ldm"), tot_cyc=rep_counter("tot_cyc"),
        imc_reads=rep_counter("imc_reads"),
        wall_time_ns=rep_counter("wall_time_ns"))
    return CompiledBundle(
        call_ids=tuple(cid for cb in bundles for cid in cb.call_ids),
        hit_lat=h[0], hit_w=h[1], hit_starts=h[2], hit_counts=h[3],
        hit_seg=h[4],
        lfb_lat=l[0], lfb_w=l[1], lfb_starts=l[2], lfb_counts=l[3],
        lfb_seg=l[4],
        miss_lat=m[0], miss_w=m[1], miss_starts=m[2], miss_counts=m[3],
        miss_seg=m[4],
        hit_wl_sum=cat("hit_wl_sum"), lfb_wl_sum=cat("lfb_wl_sum"),
        miss_w_sum=cat("miss_w_sum"), total_wl=cat("total_wl"),
        traffic=SiteTraffic(
            n_msgs=np.concatenate([cb.traffic.n_msgs for cb in bundles]),
            total_bytes=np.concatenate(
                [cb.traffic.total_bytes for cb in bundles]),
            gap_bytes=np.concatenate(
                [cb.traffic.gap_bytes for cb in bundles])),
        buffer_bytes=cat("buffer_bytes"),
        accesses_per_element=cat("accesses_per_element"),
        prefetch_frac=cat("prefetch_frac"),
        unpack=cat("unpack", bool),
        counters=counters,
        sampling_period=np.repeat(
            np.array([cb.sampling_period for cb in bundles],
                     dtype=np.float64), reps),
        baseline_runtime_ns=float(sum(cb.baseline_runtime_ns
                                      for cb in bundles)))


@dataclass(frozen=True)
class MultiSweepResult:
    """Per-bundle ``SweepResult``s priced in ONE batched evaluation.

    ``sweep_run_many`` packs every bundle into a super-bundle, prices the
    whole thing under the grid, then writes each bundle's columns into
    matrices of its own — so ``result[i]`` carries exactly what
    ``sweep_run(bundle_i, grid)`` would (same backend), while the kernel
    ran once.  Each bundle's matrices are writable float64 ``(S, c_b)``,
    possibly F-ordered views of one call-major block per matrix, and no
    two bundles share memory: writing into one leaves the others as they
    were.

    ``names`` labels the bundles (e.g. ``"prefill@64"`` / ``"decode"`` for
    a serving deployment's compiled steps).
    """

    grid: ParamGrid
    results: tuple          # one SweepResult per bundle, input order
    names: tuple = ()

    def __post_init__(self):
        if not self.names:
            object.__setattr__(
                self, "names",
                tuple(f"bundle{i}" for i in range(len(self.results))))

    def __len__(self) -> int:
        return len(self.results)

    def __iter__(self):
        return iter(self.results)

    def __getitem__(self, key) -> SweepResult:
        if isinstance(key, str):
            return self.results[self.names.index(key)]
        return self.results[key]

    # -- deployment-level aggregates -----------------------------------------
    def predicted_runtime_ns(self, weights=None, replaced=None) -> np.ndarray:
        """(S,) deployment wall time: each bundle's predicted runtime,
        weighted by how often that step runs (``weights``, default 1 each —
        e.g. ``{"decode": 128}`` for 128 decode steps per prefill)."""
        w = self._weights(weights)
        out = np.zeros(len(self.grid), dtype=np.float64)
        for wi, r in zip(w, self.results):
            out = out + wi * r.predicted_runtime_ns(replaced)
        return out

    def predicted_speedup(self, weights=None, replaced=None) -> np.ndarray:
        """(S,) deployment speedup = Σ w·baseline / Σ w·predicted (ones
        when there are no bundles — an empty deployment is a no-op)."""
        w = self._weights(weights)
        base = sum(wi * r.compiled.baseline_runtime_ns
                   for wi, r in zip(w, self.results))
        if not self.results or base == 0.0:
            return np.ones(len(self.grid), dtype=np.float64)
        return base / self.predicted_runtime_ns(weights, replaced)

    def best_scenario(self, weights=None, replaced=None) -> int:
        if len(self.grid) == 0:
            raise ValueError("best_scenario() on an empty grid: the sweep "
                             "has 0 scenarios, so there is no argmax")
        return int(np.argmax(self.predicted_speedup(weights, replaced)))

    def n_beneficial(self) -> np.ndarray:
        """(S,) beneficial call-sites across the whole deployment."""
        out = np.zeros(len(self.grid), dtype=np.int64)
        for r in self.results:
            out = out + r.n_beneficial()
        return out

    def summary_rows(self, weights=None, replaced=None) -> list:
        """One dict per scenario: varied axes + per-bundle and deployment
        speedups."""
        speed = self.predicted_speedup(weights, replaced)
        nben = self.n_beneficial()
        per = {n: r.predicted_speedup(replaced)
               for n, r in zip(self.names, self.results)}
        rows = []
        for i, lab in enumerate(self.grid.labels()):
            row = {**lab, "predicted_speedup": float(speed[i]),
                   "n_beneficial": int(nben[i])}
            for n in self.names:
                row[f"speedup[{n}]"] = float(per[n][i])
            rows.append(row)
        return rows

    def _weights(self, weights) -> list:
        if weights is None:
            return [1.0] * len(self.results)
        if hasattr(weights, "step_weights"):
            # a serve engine (or its stats): price the deployment under
            # its OBSERVED step mix — decode steps vs per-bucket prefills
            weights = weights.step_weights()
        if isinstance(weights, dict):
            return [float(weights.get(n, 1.0)) for n in self.names]
        w = list(weights)
        if len(w) != len(self.results):
            raise ValueError(f"{len(w)} weights for {len(self.results)} "
                             "bundles")
        return [float(v) for v in w]


def _sweep_plan_many(bundles, grid, plan: ExecPlan | None, names=None,
                     mpi_transfer=None, free_transfer=None
                     ) -> MultiSweepResult:
    """Multi-bundle execution core: pack every bundle into one
    offset-segment-id super-bundle (:func:`concat_bundles`), price it with
    ONE backend invocation, and write each bundle's columns straight into
    its own matrices: writable float64 ``(S, c_b)``, unchunked the
    F-ordered views of one call-major ``(c_b, S)`` block per field, in
    one float64 pass over the executor's output.  Bundles share no
    memory."""
    if plan is not None and is_streaming(plan.backend):
        raise ValueError(
            f"backend {plan.backend!r} is a streaming reducer and returns "
            "no per-bundle matrices to split; price each bundle "
            "separately, or pass a matrix backend (see known_backends())")
    bundles = list(bundles)
    names = tuple(names) if names is not None else ()
    if names and len(names) != len(bundles):
        raise ValueError(f"{len(names)} names for {len(bundles)} bundles")
    if not bundles:
        return MultiSweepResult(grid=grid, results=(), names=names)

    calls = sum(b.n_calls if isinstance(b, CompiledBundle)
                else len(b.call_sites) for b in bundles)
    with span("repro.price.pack", calls=calls):
        cbs = [b if isinstance(b, CompiledBundle) else compile_bundle(b)
               for b in bundles]
        super_cb = concat_bundles(cbs)
    plan = (plan if plan is not None else ExecPlan()).resolved()
    parts = _price_matrices(super_cb, grid, plan,
                            [cb.n_calls for cb in cbs],
                            mpi_transfer, free_transfer)
    with span("repro.price.split"):
        results = tuple(SweepResult(grid=grid, compiled=cb, plan=plan, **m)
                        for cb, m in zip(cbs, parts))
    return MultiSweepResult(grid=grid, results=results, names=names)


def sweep_run_many(bundles, grid: ParamGrid, names=None, mpi_transfer=None,
                   free_transfer=None, backend=_UNSET,
                   chunk_scenarios=_UNSET, vmap_scenarios=_UNSET,
                   pallas_interpret=_UNSET, plan: ExecPlan | None = None
                   ) -> MultiSweepResult:
    """Price MANY bundles under one scenario grid in one batched evaluation.

    Thin wrapper over the :func:`repro.core.price` multi-bundle core: the
    bundles (``TraceBundle`` or ``CompiledBundle``, mixed freely) are
    packed into a single offset-segment-id super-bundle
    (:func:`concat_bundles`) and priced with one backend invocation for
    ALL steps x scenarios, then split back into per-bundle
    ``SweepResult``s.  Execution config travels in ``plan``
    (:class:`ExecPlan`); the per-call ``backend=`` / ``chunk_scenarios=``
    / ``vmap_scenarios=`` / ``pallas_interpret=`` kwargs are DEPRECATED
    shims (bit-identical, one ``DeprecationWarning`` per call).

    This is the serving deployment's advisor path: compile each engine
    step (prefill buckets + decode) once, price the whole deployment's
    collectives under the grid in one call (``price(engine, grid)``).
    """
    plan = legacy_plan(plan, "sweep_run_many", backend=backend,
                       chunk_scenarios=chunk_scenarios,
                       vmap_scenarios=vmap_scenarios,
                       pallas_interpret=pallas_interpret)
    return _sweep_plan_many(bundles, grid, plan, names,
                            mpi_transfer, free_transfer)
