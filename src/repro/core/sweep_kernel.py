"""Backend-pluggable grid-pricing kernel for the scenario sweep.

``price_grid(cb, view, xp)`` is the pure, array-module-generic body of the
sweep: characterization weights -> bracket terms (segment sums over the
packed samples) -> ``category_bracket``/``combine_categories``/
``unpack_blend`` -> transfer models.  The SAME function runs under three
executors:

  * :func:`price_grid_numpy` — ``xp = numpy``; segment sums via
    ``np.add.reduceat``.  ``sweep_run`` adds scenario-axis chunking on top,
    so peak memory is ``O(chunk x n_samples)`` with bit-identical results.
  * :func:`price_grid_jax` — ``xp = jax.numpy`` under ``jax.jit`` (one
    compilation per compiled bundle, cached); segment sums via
    ``jax.ops.segment_sum`` imported through ``repro.compat``.  The kernel
    is ``vmap``-able over the scenario axis (``vmap_scenarios=True`` maps
    the per-scenario kernel instead of broadcasting), so grids run on
    accelerators and compose with outer ``vmap``s over bundles.  View
    buffers are NOT donated — a jax-array-backed view can be priced any
    number of times.
  * :func:`price_grid_pallas` — like the jax executor, but the four
    scenario-dependent bracket aggregates come from the fused Pallas kernel
    in ``repro.kernels.sweep_bracket``: bracket terms are computed and
    segment-reduced in VMEM scratch while tiling the ``(scenarios,
    packed_samples)`` plane, so the ``(S, n_samples)`` intermediates never
    reach HBM.  The kernel is compiled by Mosaic on TPU, where it prices
    in float32, and interpreted elsewhere (in float64 by default) — how
    the CPU test suite exercises the real kernel.

The physics stays written once: the bracket formulas live in
``access.BracketTerms``/``category_bracket`` and the transfer models expose
``transfer_from_traffic`` — all of them take the explicit array namespace
``xp`` and are called here with ``(n_scenarios, n_sites)`` arrays, by the
scalar per-call predictor with floats.  (The fused Pallas kernel is the one
deliberate restatement of the scenario-dependent bracket terms; its parity
is pinned against the unfused path by ``tests/test_sweep_backends.py`` and
``tests/test_kernels.py``.)

Scenario-dependent inputs arrive through the ``view`` (``ParamGrid.view()``):
every numeric ``ModelParams`` field as an ``(S, 1)`` array, threshold pairs
as lower/upper arrays, and — for the categorical ``mpi_transfer=`` /
``free_transfer=`` grid axes — a static tuple of candidate transfer models
plus an ``(S, 1)`` integer code selecting one per scenario.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .access import (BracketTerms, category_bracket, combine_categories,
                     unpack_blend)
from .characterization import ALL_CATEGORIES, Characterization
from .spans import span
from .transfer import SiteTraffic

#: The ``(n_scenarios, n_calls)`` component matrices a sweep produces, in
#: ``SweepResult`` field order.  ``price_grid`` returns a dict with exactly
#: these keys; ``sweep_run`` builds every ``SweepResult`` (including the
#: empty-grid case) from this one list, so adding a component is a
#: two-line change (here + the dataclass field).
MATRIX_FIELDS = ("t_transfer_mpi_ns", "t_transfer_cxl_ns",
                 "t_access_mpi_ns", "t_access_cxl_ns")


# --------------------------------------------------------------------------
# Segment sums (per-site reductions over the packed sample axis)
# --------------------------------------------------------------------------

def _segment_sum_np(x: np.ndarray, starts: np.ndarray,
                    counts: np.ndarray) -> np.ndarray:
    """Row-wise per-site sums of packed sample terms.

    ``np.add.reduceat`` returns ``x[start]`` (not 0) for empty segments, so
    empties are masked out explicitly.
    """
    n = x.shape[-1]
    n_seg = len(starts)
    if n == 0 or n_seg == 0:
        return np.zeros(x.shape[:-1] + (n_seg,), dtype=x.dtype)
    # pad one zero so a start index of ``n`` (empty trailing segment) is
    # valid WITHOUT clipping — clipping would shorten the previous segment
    pad = np.zeros(x.shape[:-1] + (1,), dtype=x.dtype)
    out = np.add.reduceat(np.concatenate([x, pad], axis=-1), starts, axis=-1)
    return np.where(counts > 0, out, np.zeros((), dtype=x.dtype))


def _segment_sum(x, starts, counts, seg_ids, n_seg, xp, impl=None,
                 interpret=None):
    """Backend dispatch: reduceat (numpy), ``jax.ops.segment_sum`` (jax),
    or the tiled Pallas kernel (``impl="pallas"``; ``interpret=None``
    compiles it on TPU and interprets it elsewhere).

    ``x``'s LAST axis is the packed-sample axis; the result replaces it
    with an ``n_seg`` per-site axis.  Both encodings of the segmentation
    travel in ``CompiledBundle`` (starts/counts for reduceat, per-sample
    segment ids for scatter-style backends).
    """
    if impl == "pallas":
        from ..kernels.sweep_bracket import segment_sum_pallas
        return segment_sum_pallas(x, seg_ids, n_seg, interpret=interpret)
    if xp is np:
        return _segment_sum_np(x, starts, counts)
    from ..compat import segment_sum
    out = segment_sum(xp.moveaxis(xp.asarray(x), -1, 0), seg_ids,
                      num_segments=n_seg, indices_are_sorted=True)
    return xp.moveaxis(out, 0, -1)


# --------------------------------------------------------------------------
# The kernel
# --------------------------------------------------------------------------

def _select_transfer(models, code, traffic, xp):
    """Per-scenario transfer time: evaluate every candidate model (fields
    broadcast ``(S, 1)``) and select by the scenario's integer code."""
    t = models[0].transfer_from_traffic(traffic, xp=xp)
    for k in range(1, len(models)):
        t = xp.where(code == k,
                     models[k].transfer_from_traffic(traffic, xp=xp), t)
    return t


def _bracket_seg_terms(cb, delta, cxl_lat, xp) -> dict:
    """The four scenario-dependent bracket aggregates — the unfused path:
    one ``(S, n_samples)`` term per bracket, materialized then
    segment-summed to ``(S, n_calls)``.  ``price_grid_pallas`` swaps this
    stage for the fused Pallas kernel via the ``bracket_terms=`` hook."""
    asx = xp.asarray
    hit_w, hit_lat = asx(cb.hit_w), asx(cb.hit_lat)
    lfb_w, lfb_lat = asx(cb.lfb_w), asx(cb.lfb_lat)
    miss_w, miss_lat = asx(cb.miss_w), asx(cb.miss_lat)

    def seg(x, grp):
        return _segment_sum(x, getattr(cb, grp + "_starts"),
                            getattr(cb, grp + "_counts"),
                            asx(getattr(cb, grp + "_seg")), cb.n_calls, xp)

    return {
        "hit_degraded": seg(hit_w * xp.maximum(hit_lat + delta, 0.0), "hit"),
        "lfb_mem": seg(lfb_w * xp.maximum(lfb_lat + delta, 0.0), "lfb"),
        "lfb_half": seg(lfb_w * xp.maximum(lfb_lat + delta / 2.0, 0.0),
                        "lfb"),
        "miss_congested": seg(miss_w * xp.maximum(cxl_lat, miss_lat + delta),
                              "miss"),
    }


def price_grid(cb, view, xp, bracket_terms=None) -> dict:
    """Price one compiled bundle under every scenario of ``view``.

    Pure in its array inputs: ``cb`` contributes scenario-independent
    constants, ``view`` the per-scenario parameters, and ``xp`` the array
    namespace (``numpy`` or ``jax.numpy`` — under ``jax.jit``/``vmap`` the
    view fields are tracers and everything traces through).

    ``cb.counters`` / ``cb.sampling_period`` may be per-bundle scalars OR
    ``(n_calls,)`` arrays (the ``sweep_run_many`` super-bundle, where each
    call-site carries its originating bundle's counters); every use below
    is elementwise, so both broadcast identically.

    ``bracket_terms`` (default :func:`_bracket_seg_terms`) supplies the
    four scenario-dependent bracket aggregates as ``fn(cb, delta, cxl_lat,
    xp) -> {name: (S, n_calls)}`` — the seam the fused Pallas kernel plugs
    into.

    Returns ``{field: matrix}`` for :data:`MATRIX_FIELDS`; each matrix
    broadcasts to ``(n_scenarios, n_calls)`` (executors normalize shapes).
    """
    v = view
    asx = xp.asarray

    # -- characterization (same code path as the scalar predictor) ----------
    ch = Characterization.from_counters(cb.counters, v, xp=xp)  # (S, 1)
    # scenario-independent, so computed on the host in float64: on TPU,
    # where float64 is emulated by float32 pairs, XLA's simplifier
    # reassociates the pair sum of a constant and a traced value (the
    # ``1 - f_first`` below) and drops its low word
    n = np.maximum(1.0, cb.accesses_per_element)                # (C,)
    f_first = 1.0 / n
    weights = {c: f_first * asx(ch.first[c])
               + (1.0 - f_first) * asx(ch.subsequent[c])
               for c in ALL_CATEGORIES}                         # (S, C)

    # -- access model: Eq. 5 baseline + Eq. 6-10 re-pricing ------------------
    cxl_lat = asx(v.cxl_lat_ns)
    delta = cxl_lat - asx(v.mem_lat_ns)                         # (S, 1)
    segd = (bracket_terms or _bracket_seg_terms)(cb, delta, cxl_lat, xp)

    terms = BracketTerms(
        hit=asx(cb.hit_wl_sum),
        hit_degraded=segd["hit_degraded"],
        lfb_plain=asx(cb.lfb_wl_sum),
        lfb_mem=segd["lfb_mem"],
        lfb_half=segd["lfb_half"],
        miss_flat=cxl_lat * asx(cb.miss_w_sum),
        miss_congested=segd["miss_congested"])

    brackets = {c: category_bracket(c, terms, cb.prefetch_frac, xp=xp)
                for c in ALL_CATEGORIES}
    t_cxl = combine_categories(brackets, weights, v, xp=xp)     # (S, C)
    t_ddr = combine_categories(
        {c: cb.total_wl for c in ALL_CATEGORIES}, weights, v, xp=xp)
    t_cxl = unpack_blend(t_cxl, t_ddr, f_first, asx(cb.unpack), xp=xp)

    # -- transfer model (shared transfer_from_traffic core) ------------------
    traffic = SiteTraffic(n_msgs=asx(cb.traffic.n_msgs),
                          total_bytes=asx(cb.traffic.total_bytes),
                          gap_bytes=asx(cb.traffic.gap_bytes))
    return {
        "t_transfer_mpi_ns": _select_transfer(
            v.mpi_transfer_models, asx(v.mpi_transfer_code), traffic, xp),
        "t_transfer_cxl_ns": _select_transfer(
            v.free_transfer_models, asx(v.free_transfer_code), traffic, xp),
        "t_access_mpi_ns": t_ddr * cb.sampling_period,
        "t_access_cxl_ns": t_cxl * cb.sampling_period,
    }


# --------------------------------------------------------------------------
# NumPy executor
# --------------------------------------------------------------------------

def price_grid_numpy(cb, view) -> dict:
    """One broadcasted NumPy pass (chunking, if any, happens in
    ``sweep_run`` by slicing the view — bit-identical because every row is
    computed independently)."""
    return price_grid(cb, view, np)


# --------------------------------------------------------------------------
# jax.jit / Pallas executors
# --------------------------------------------------------------------------

_JAX = None            # (jax, jnp) once imported + pytrees registered


def _register_pytrees(jax) -> None:
    """Register the view and transfer-model containers as pytrees so the
    whole view travels as ONE jit argument (vmap-able)."""
    from jax.tree_util import register_pytree_node

    from .sweep import _ParamArrays, _ThresholdView
    from .transfer import (HockneyTransfer, LogGPTransfer,
                           MessageFreeTransfer)

    def reg_dataclass(cls):
        names = tuple(f.name for f in dataclasses.fields(cls))
        register_pytree_node(
            cls,
            lambda obj, _n=names: (tuple(getattr(obj, n) for n in _n), None),
            lambda aux, ch, _c=cls, _n=names: _c(**dict(zip(_n, ch))))

    for cls in (HockneyTransfer, LogGPTransfer, MessageFreeTransfer):
        reg_dataclass(cls)

    register_pytree_node(
        _ThresholdView,
        lambda tv: ((tv.lower, tv.upper), None),
        lambda aux, ch: _ThresholdView(*ch))

    def flatten_view(v):
        keys = tuple(sorted(v.__dict__))
        return tuple(v.__dict__[k] for k in keys), keys

    def unflatten_view(keys, children):
        v = object.__new__(_ParamArrays)
        v.__dict__.update(zip(keys, children))
        return v

    register_pytree_node(_ParamArrays, flatten_view, unflatten_view)


def _ensure_jax():
    global _JAX
    if _JAX is None:
        import jax
        import jax.numpy as jnp
        _register_pytrees(jax)
        _JAX = (jax, jnp)
    return _JAX


def _jitted_price(cb, key, make_run):
    """Per-bundle compile cache: the bundle's packed arrays are closed over
    as constants (compile once, evaluate many grids); the view is the
    argument.  View buffers are deliberately NOT donated — a caller that
    builds a jax-array-backed view may price it any number of times
    (donation used to delete its buffers on the first call).

    The cache lives ON the bundle (attached via ``object.__setattr__`` —
    it's a frozen dataclass), so the jitted executables and the closed-over
    arrays die with the bundle instead of accumulating in a module-level
    registry for the process lifetime.  The super-bundle of a multi-bundle
    price is the one bundle kept beyond its call: ``sweep._super_bundle``
    holds those of the last few distinct bundle lists, a fixed number, so
    re-pricing a list runs its executable again without a retrace while
    a process pricing ever-new lists still holds a bounded set.

    Returns ``(fn, miss)``: ``miss`` is True when this call built a new
    ``jax.jit`` (its first call then traces and lowers again).
    """
    cache = getattr(cb, "_jit_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(cb, "_jit_cache", cache)
    fn = cache.get(key)
    if fn is not None:
        return fn, False
    jax, _ = _ensure_jax()
    fn = cache[key] = jax.jit(make_run())
    return fn, True


def _grid_jit(cb, vmap_scenarios: bool = False, x64: bool = True):
    """The cached jitted executable behind :func:`price_grid_jax` (its
    one argument is the view) and whether it was built now — split out so
    ``repro.analysis.ircheck`` can trace/lower exactly what production
    runs without executing it."""
    jax, jnp = _ensure_jax()

    def make_run():
        if not vmap_scenarios:
            return lambda v: price_grid(cb, v, jnp)

        def run(v):
            # map only leaves carrying the scenario axis; scalar leaves
            # (e.g. a float field of an override transfer model)
            # broadcast into every per-scenario call
            leaves, treedef = jax.tree_util.tree_flatten(v)
            s = v.mem_lat_ns.shape[0]
            axes = [0 if getattr(x, "ndim", 0) >= 1 and x.shape[0] == s
                    else None for x in leaves]

            def per_row(*row_leaves):
                row = jax.tree_util.tree_unflatten(treedef, row_leaves)
                return price_grid(cb, row, jnp)

            return jax.vmap(per_row, in_axes=axes)(*leaves)
        return run

    return _jitted_price(cb, ("jax", bool(vmap_scenarios), bool(x64)),
                         make_run)


def price_grid_jax(cb, view, vmap_scenarios: bool = False,
                   x64: bool = True) -> dict:
    """Evaluate the grid under ``jax.jit`` (double precision by default,
    scoped via ``repro.compat.enable_x64`` so the process-global x64 flag
    is never touched; ``x64=False`` prices in the ambient f32).

    ``vmap_scenarios=True`` runs ``jax.vmap`` of the per-scenario kernel
    over the scenario axis instead of the broadcasted batch formulation —
    same results, and the shape accelerator sharding composes with.

    Returns the matrices as device arrays; the sweep core copies them to
    the host.
    """
    fn, miss = _grid_jit(cb, vmap_scenarios, x64)
    return _run_jitted(fn, miss, x64, view)


def _run_jitted(fn, miss: bool, x64: bool, *args) -> dict:
    """Call a bundle's jitted executable on ``args`` in the
    ``repro.price.run`` span; its outputs stay on the device (the sweep
    core fetches matrices, :func:`price_topk_chunk` its reductions)."""
    with _precision_scope(x64), span("repro.price.run", jit_miss=int(miss)):
        return fn(*args)


def _precision_scope(x64: bool):
    """Scoped x64 (the parity-pinned default) or the ambient precision."""
    if x64:
        from ..compat import enable_x64
        return enable_x64()
    import contextlib
    return contextlib.nullcontext()


# --------------------------------------------------------------------------
# Pallas executor (fused bracket + segment sum)
# --------------------------------------------------------------------------

def pallas_modes(interpret: bool | None = None,
                 x64: bool | None = None) -> tuple:
    """``(interpret, x64)`` for the Pallas executor, ``None`` resolved
    from the platform: compiled Mosaic in float32 on TPU, the interpreter
    in float64 elsewhere.  Mosaic has no 64-bit types, so a compiled
    kernel at x64 is refused here instead of by the TPU compiler."""
    from ..kernels import resolve_interpret
    interpret = resolve_interpret(interpret)
    x64 = interpret if x64 is None else bool(x64)
    if x64 and not interpret:
        raise ValueError("the compiled Pallas sweep kernel has no float64 "
                         "(Mosaic supports 32-bit types only): price with "
                         "x64=False, or interpret=True")
    return interpret, x64


def price_grid_pallas(cb, view, interpret: bool | None = None,
                      x64: bool | None = None) -> dict:
    """Evaluate the grid with the fused Pallas bracket/segment-sum kernel.

    Identical to :func:`price_grid_jax` except the four scenario-dependent
    bracket aggregates come from ``repro.kernels.sweep_bracket``: the
    ``(scenarios, packed_samples)`` plane is tiled and the ``w * max(lat +
    delta, 0)``-style terms are computed AND segment-reduced per site in
    VMEM scratch, so those intermediates never reach HBM.  The bundle's
    packed groups enter in the pallas-friendly padded layout of
    ``CompiledBundle.padded_groups``.

    ``interpret`` / ``x64`` default to the platform (:func:`pallas_modes`):
    the compiled kernel in float32 on TPU, the interpreter in float64
    elsewhere (the CPU validation mode).  Returns device arrays, as
    :func:`price_grid_jax` does.
    """
    interpret, x64 = pallas_modes(interpret, x64)
    _, jnp = _ensure_jax()

    def make_run():
        from ..kernels.sweep_bracket import fused_bracket_segsum
        groups = cb.padded_groups()

        def bracket_terms(cb_, delta, cxl_lat, xp):
            return fused_bracket_segsum(
                groups["hit"], groups["lfb"], groups["miss"], delta,
                cxl_lat, cb_.n_calls, interpret=interpret)

        return lambda v: price_grid(cb, v, jnp, bracket_terms=bracket_terms)

    fn, miss = _jitted_price(cb, ("pallas", bool(interpret), bool(x64)),
                             make_run)
    return _run_jitted(fn, miss, x64, view)


# --------------------------------------------------------------------------
# Distributed executor primitive (sharded chunk -> streaming top-k)
# --------------------------------------------------------------------------

#: Speedup histogram bin edges shared by the streaming reducer and its
#: numpy reference: bucket ``j = searchsorted(edges, sp, side="right")``,
#: giving ``len(edges) + 1`` segments — ``j = 0`` is the ``sp < edges[0]``
#: underflow, ``j = len(edges)`` the ``sp >= edges[-1]`` overflow.
SPEEDUP_HIST_EDGES = np.linspace(0.0, 2.0, 41)

#: Scenario-axis chunk the distributed executor streams by default: large
#: enough to keep 4-16 shards busy, small enough that each shard's
#: ``(chunk / n_devices, n_calls)`` working set stays a few MB.
DIST_CHUNK_DEFAULT = 65536


def _topk_chunk_plan(cb, view, valid, idx, k, n_devices: int = 1,
                     x64: bool = True):
    """Validate one chunk's shard geometry and build ``(jitted fn, flat
    args, jit miss)`` — the executable :func:`price_topk_chunk` runs
    (``fn(*flat)``) and ``repro.analysis.ircheck`` traces/lowers for the
    collective and liveness passes without executing."""
    jax, jnp = _ensure_jax()
    from jax.sharding import PartitionSpec as P

    from ..compat import device_mesh_1d, segment_sum, shard_map

    valid = np.asarray(valid, dtype=bool)
    idx = np.asarray(idx, dtype=np.int64)
    n_pad = valid.shape[0]
    n_dev = int(n_devices)
    if n_pad == 0 or n_pad % n_dev:
        raise ValueError(f"chunk of {n_pad} padded scenarios does not "
                         f"shard evenly over {n_dev} devices")
    k_local = int(min(k, n_pad // n_dev))
    if k_local < 1:
        raise ValueError(f"topk must be >= 1, got {k}")

    leaves, treedef = jax.tree_util.tree_flatten(view)
    sharded = tuple(getattr(x, "ndim", 0) >= 1
                    and getattr(x, "shape", (0,))[0] == n_pad
                    for x in leaves)
    key = ("dist", n_dev, n_pad, k_local, bool(x64), treedef, sharded)

    def make_run():
        mesh = device_mesh_1d(n_dev)
        n_hist = len(SPEEDUP_HIST_EDGES) + 1

        def shard_fn(valid_s, idx_s, *leaves_s):
            v = jax.tree_util.tree_unflatten(treedef, leaves_s)
            mats = price_grid(cb, v, jnp)
            n_loc = valid_s.shape[0]
            gain = jnp.broadcast_to(
                (mats["t_transfer_mpi_ns"] + mats["t_access_mpi_ns"])
                - (mats["t_transfer_cxl_ns"] + mats["t_access_cxl_ns"]),
                (n_loc, cb.n_calls))
            base = cb.baseline_runtime_ns
            sp = base / (base - gain.sum(axis=-1))           # (n_loc,)

            spv = jnp.where(valid_s, sp, -jnp.inf)
            top_val, pos = jax.lax.top_k(spv, k_local)
            fkey = jnp.where(valid_s, -jnp.abs(sp - 1.0), -jnp.inf)
            _, fpos = jax.lax.top_k(fkey, k_local)

            vf = valid_s.astype(sp.dtype)
            bucket = jnp.searchsorted(jnp.asarray(SPEEDUP_HIST_EDGES), sp,
                                      side="right")
            out = {
                "top_val": top_val,
                "top_idx": idx_s[pos],
                "top_ok": valid_s[pos],
                "front_val": sp[fpos],
                "front_idx": idx_s[fpos],
                "front_ok": valid_s[fpos],
                "count": vf.sum(),
                "sp_sum": jnp.where(valid_s, sp, 0.0).sum(),
                "sp_min": jnp.where(valid_s, sp, jnp.inf).min(),
                "sp_max": jnp.where(valid_s, sp, -jnp.inf).max(),
                "hist": segment_sum(vf, bucket, num_segments=n_hist),
                "n_beneficial": ((gain > 0) & valid_s[:, None]).sum(axis=0),
                "gain_sum": jnp.where(valid_s[:, None], gain, 0.0)
                               .sum(axis=0),
            }
            # every output gains a unit shard axis so out_specs can stack
            # the n_dev shards along it
            return {name: val[None] for name, val in out.items()}

        in_specs = (P("scenarios"), P("scenarios")) + tuple(
            P("scenarios") if s else P() for s in sharded)
        return shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=P("scenarios"))

    fn, miss = _jitted_price(cb, key, make_run)
    return fn, (valid, idx) + tuple(leaves), miss


def price_topk_chunk(cb, view, valid, idx, k, n_devices: int = 1,
                     x64: bool = True) -> dict:
    """Price ONE padded scenario chunk sharded over ``n_devices`` and
    reduce it on-device to per-shard candidates + exact aggregates — the
    inner step of the streaming ``"distributed"`` backend.  The full
    ``(chunk, n_calls)`` component matrices exist only shard-local inside
    the jitted computation; nothing bigger than ``O(chunk / n_devices x
    n_calls)`` is ever materialized per device, and only ``O(n_devices x
    k)`` candidate rows plus ``O(n_calls)`` aggregates come back to host.

    ``view`` must be padded so every pytree leaf carrying the scenario
    axis has leading dim ``n_pad`` with ``n_pad % n_devices == 0``
    (``_ParamArrays._pad`` / ``compat.padded_size``); ``valid`` is the
    ``(n_pad,)`` bool mask of real rows and ``idx`` their ``(n_pad,)``
    global scenario indices.  Keeping ``n_pad`` constant across chunks
    reuses one compiled executable for the whole sweep (the compile cache
    lives on the bundle, keyed by shard geometry + view structure).

    Returns numpy arrays, each with a leading ``n_devices`` shard axis
    (host code merges shards):

      * ``top_val`` / ``top_idx`` / ``top_ok`` — ``(n_dev, k)`` best
        predicted speedups per shard (masked rows carry ``-inf`` /
        ``ok=False``), their global indices, and validity.
      * ``front_val`` / ``front_idx`` / ``front_ok`` — ``(n_dev, k)``
        scenarios closest to speedup 1.0 (the refinement frontier);
        ``front_val`` is the actual speedup, ordering happened on-device
        by ``-|sp - 1|``.
      * ``count`` / ``sp_sum`` / ``sp_min`` / ``sp_max`` — ``(n_dev,)``
        exact per-shard speedup aggregates over valid rows.
      * ``hist`` — ``(n_dev, len(SPEEDUP_HIST_EDGES) + 1)`` speedup
        histogram counts.
      * ``n_beneficial`` / ``gain_sum`` — ``(n_dev, n_calls)`` per-call
        beneficial-scenario counts and summed gains over valid rows.
    """
    fn, flat, miss = _topk_chunk_plan(cb, view, valid, idx, k,
                                      n_devices=n_devices, x64=x64)
    out = _run_jitted(fn, miss, x64, *flat)
    with span("repro.price.fetch"):
        return {name: np.asarray(v) for name, v in out.items()}


# --------------------------------------------------------------------------
# IR-checked entry points (repro.analysis.ircheck registrations)
# --------------------------------------------------------------------------

def _ircheck_bundle():
    """Small deterministic compiled bundle: every data-source class, two
    call-sites, enough samples that the traced configurations are shaped
    like real sweeps (the IR passes care about structure, not values)."""
    from .sweep import compile_bundle
    from .traces import (CommRecord, CounterSet, DataSource, LoadSample,
                         TraceBundle)
    bundle = TraceBundle(sampling_period=500.0)
    bundle.counters = CounterSet(ld_ins=5e9, l1_ldm=6e8, l3_ldm=9e7,
                                 tot_cyc=3.1e9, imc_reads=2.2e8,
                                 wall_time_ns=1.5e9)
    sources = tuple(DataSource)
    for i, cid in enumerate(("recv_a", "recv_b")):
        for j in range(12):
            bundle.add_sample(LoadSample(
                call_id=cid, lat_ns=30.0 + 17.0 * ((3 * i + j) % 13),
                source=sources[(i + j) % len(sources)],
                weight=1.0 + 0.25 * j))
        bundle.add_comm(CommRecord(call_id=cid, bytes=4096 * (i + 1),
                                   count=3))
    return compile_bundle(bundle)


def _ircheck_grid_spec():
    from ..analysis.ircheck import EntrySpec, src_for
    from .params import ModelParams
    from .sweep import ParamGrid, _scenario_view

    cb = _ircheck_bundle()
    grid = ParamGrid.product(ModelParams.multinode(),
                             cxl_lat_ns=[300.0, 400.0, 500.0, 600.0],
                             cxl_atomic_lat_ns=[350.0, 550.0])
    return EntrySpec(name="sweep.price_grid_jax", fn=_grid_jit(cb)[0],
                     args=(_scenario_view(grid),), x64=True,
                     src=src_for(price_grid_jax))


def _ircheck_topk_spec():
    from ..analysis.ircheck import EntrySpec, src_for
    from .params import ModelParams
    from .sweep import ParamGrid, _scenario_view

    n_dev, S, k = 4, 8, 4
    cb = _ircheck_bundle()
    grid = ParamGrid.sample(ModelParams.multinode(), S, seed=0,
                            cxl_lat_ns=(250.0, 700.0),
                            cxl_atomic_lat_ns=(300.0, 800.0))
    view = _scenario_view(grid)
    valid = np.ones(S, dtype=bool)
    idx = np.arange(S, dtype=np.int64)
    fn, flat, _ = _topk_chunk_plan(cb, view, valid, idx, k,
                                   n_devices=n_dev, x64=True)
    return EntrySpec(name="sweep.price_topk_chunk", fn=fn, args=flat,
                     x64=True, min_devices=n_dev,
                     mesh_axes={"scenarios": n_dev},
                     src=src_for(price_topk_chunk))


def register_ircheck_entrypoints(register) -> None:
    """Register the sweep kernels' representative traced configurations
    with ``repro.analysis.ircheck`` (called by its ``_load_builtins``)."""
    register("sweep.price_grid_jax", _ircheck_grid_spec)
    register("sweep.price_topk_chunk", _ircheck_topk_spec, min_devices=4)
