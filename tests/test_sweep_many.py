"""Multi-bundle batched sweeps: ``sweep_run_many`` parity with per-bundle
``sweep_run`` on every backend (numpy / jax / pallas-interpret), the
empty/single/zero-call edge cases, deployment-level aggregates, and the
``CommAdvisor.sweep_text_many`` flow."""
import itertools

import numpy as np
import pytest

from repro.core import (ArraySet, CommAdvisor, CommRecord, CounterSet,
                        DataSource, ExecPlan, HockneyTransfer, LoadSample,
                        ModelParams, MultiSweepResult, ParamGrid, TraceBundle,
                        compile_bundle, concat_bundles, price, sweep_run,
                        sweep_run_many)
from repro.core.execplan import resolve_backend
from repro.core.sweep import _scenario_view
from repro.core.sweep_kernel import MATRIX_FIELDS

RTOL = 1e-9           # acceptance bound: super-bundle == per-bundle runs
BACKENDS = ("numpy", "jax", "pallas")


def make_bundle(seed: int, n_sites: int, period: float,
                wall: float) -> TraceBundle:
    """Small synthetic bundle; counters/period differ per bundle so the
    per-call counter repeat in the super-bundle actually matters."""
    rng = np.random.default_rng(seed)
    b = TraceBundle(sampling_period=period)
    b.counters = CounterSet(ld_ins=4e9 * (1 + seed), l1_ldm=5e8 + 1e8 * seed,
                            l3_ldm=8e7, tot_cyc=3e9, imc_reads=2e8,
                            wall_time_ns=wall)
    sources = list(DataSource)
    for i in range(n_sites):
        cid = f"b{seed}_recv{i}"
        for k in range(6 + 3 * i):
            b.add_sample(LoadSample(
                call_id=cid, lat_ns=float(rng.uniform(5, 400)),
                source=sources[(i + k) % len(sources)],
                weight=float(rng.uniform(0.5, 3.0))))
        b.add_comm(CommRecord(call_id=cid, bytes=2048 * (i + 1), count=1 + i))
        site = b.call(cid)
        site.accesses_per_element = 1.0 + 0.7 * i
        site.loads_per_line = 1.0 + i
    if n_sites:
        b.call(f"b{seed}_recv0").unpack = True
    return b


@pytest.fixture(scope="module")
def bundles():
    return [make_bundle(0, 3, 500.0, 1.5e9),
            make_bundle(1, 2, 900.0, 2.5e9),
            make_bundle(2, 4, 100.0, 0.8e9)]


@pytest.fixture(scope="module")
def grid():
    return ParamGrid.product(ModelParams.multinode(),
                             cxl_lat_ns=[250.0, 350.0, 500.0],
                             cxl_atomic_lat_ns=[350.0, 653.0])


@pytest.fixture(scope="module")
def latencies():
    """Only the CXL latencies vary; every other field is one ``(1, 1)``
    value, so ``t_access_mpi_ns`` comes back ``(1, n_calls)``."""
    rng = np.random.default_rng(7)
    n = 11
    return ArraySet(base=ModelParams.multinode(), n=n,
                    columns={"cxl_lat_ns": rng.uniform(250.0, 700.0, n),
                             "cxl_atomic_lat_ns": rng.uniform(300.0, 800.0,
                                                              n)},
                    cat={}, ranges={})


def _assert_matches(multi, singles, ctx=""):
    assert len(multi) == len(singles)
    for i, (rm, rs) in enumerate(zip(multi, singles)):
        assert rm.call_ids == rs.call_ids
        for f in MATRIX_FIELDS:
            a, b = getattr(rm, f), getattr(rs, f)
            assert a.shape == b.shape
            err = np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)) \
                if a.size else 0.0
            assert err <= RTOL, (ctx, i, f, err)


@pytest.mark.parametrize("backend", BACKENDS)
def test_matches_per_bundle_runs(bundles, grid, backend):
    """ACCEPTANCE: one batched super-bundle evaluation == N per-bundle
    sweeps at 1e-9 on every backend."""
    singles = [sweep_run(b, grid, backend=backend) for b in bundles]
    multi = sweep_run_many(bundles, grid, backend=backend)
    _assert_matches(multi, singles, backend)


def test_numpy_super_bundle_is_bit_identical(bundles, grid):
    """The numpy path is elementwise in the per-call counter arrays, so the
    super-bundle run is not merely close — it is bit-identical."""
    singles = [sweep_run(b, grid) for b in bundles]
    multi = sweep_run_many(bundles, grid)
    for rm, rs in zip(multi, singles):
        for f in MATRIX_FIELDS:
            np.testing.assert_array_equal(getattr(rm, f), getattr(rs, f))


@pytest.mark.parametrize("backend", BACKENDS)
def test_single_bundle_list(bundles, grid, backend):
    multi = sweep_run_many(bundles[:1], grid, backend=backend,
                           names=["only"])
    _assert_matches(multi, [sweep_run(bundles[0], grid, backend=backend)])
    assert multi.names == ("only",)
    assert multi["only"] is multi[0]


def _super_columns(cbs, scenarios, backend, mpi_transfer):
    """The reference assembly: the super-bundle's raw executor output
    widened to float64, broadcast to ``(S, C)`` and cut per bundle."""
    sup = concat_bundles(cbs)
    out = resolve_backend(backend)(
        sup, _scenario_view(scenarios, mpi_transfer),
        ExecPlan(backend=backend).resolved())
    full = {f: np.broadcast_to(np.asarray(out[f], np.float64),
                               (len(scenarios), sup.n_calls))
            for f in MATRIX_FIELDS}
    ends = np.cumsum([cb.n_calls for cb in cbs])
    cols = [{f: m[:, hi - cb.n_calls:hi] for f, m in full.items()}
            for cb, hi in zip(cbs, ends)]
    return cols, {f: np.shape(out[f]) for f in MATRIX_FIELDS}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", ["grid", "latencies", "latencies+hockney"])
def test_assembly_bit_identical(bundles, grid, latencies, backend, case):
    """Each bundle's matrices equal, bit for bit, both its own single run
    and its columns of the super-bundle's executor output — also where a
    field has no scenario axis (the broadcast branch) or no 2-D shape at
    all (a scalar-field transfer override)."""
    scenarios = grid if case == "grid" else latencies
    mpi = HockneyTransfer(320.0, 9.4) if case.endswith("hockney") else None
    cbs = [compile_bundle(b) for b in bundles]
    multi = price(cbs, scenarios, plan=backend, mpi_transfer=mpi)
    cols, shapes = _super_columns(cbs, scenarios, backend, mpi)
    if case != "grid":
        assert shapes["t_access_mpi_ns"][0] == 1
    if mpi is not None:
        assert len(shapes["t_transfer_mpi_ns"]) == 1
    for cb, rm, want in zip(cbs, multi, cols):
        rs = price(cb, scenarios, plan=backend, mpi_transfer=mpi)
        for f in MATRIX_FIELDS:
            np.testing.assert_array_equal(getattr(rm, f), want[f])
            np.testing.assert_array_equal(getattr(rm, f), getattr(rs, f))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("chunk", [None, 4])
def test_bundle_matrices_are_their_own(bundles, latencies, backend, chunk):
    """Every bundle's matrices are writable float64 ``(S, c_b)`` that share
    memory with no other, so an in-place write to one leaves the rest."""
    multi = price(bundles, latencies,
                  plan=ExecPlan(backend=backend, chunk_scenarios=chunk))
    mats = [getattr(r, f) for r in multi for f in MATRIX_FIELDS]
    for r in multi:
        for f in MATRIX_FIELDS:
            m = getattr(r, f)
            assert m.dtype == np.float64 and m.flags.writeable
            assert m.shape == (len(latencies), r.compiled.n_calls)
            if chunk is not None:
                assert m.flags.c_contiguous
    for a, b in itertools.combinations(mats, 2):
        assert not np.shares_memory(a, b)
    before = [m.copy() for m in mats]
    mats[0] *= -1.0
    for m, old in zip(mats[1:], before[1:]):
        np.testing.assert_array_equal(m, old)


def test_empty_bundle_list(grid):
    multi = sweep_run_many([], grid)
    assert isinstance(multi, MultiSweepResult) and len(multi) == 0
    assert list(multi) == []
    np.testing.assert_array_equal(multi.predicted_speedup(),
                                  np.ones(len(grid)))
    assert multi.summary_rows()[0]["predicted_speedup"] == 1.0


def test_zero_call_bundle_in_the_middle(bundles, grid):
    empty = TraceBundle(sampling_period=123.0)
    empty.counters = CounterSet(ld_ins=1e9, wall_time_ns=1e9)
    mix = [bundles[0], empty, bundles[1]]
    multi = sweep_run_many(mix, grid)
    assert multi[1].gain_ns.shape == (len(grid), 0)
    _assert_matches(MultiSweepResult(grid=grid,
                                     results=(multi[0], multi[2])),
                    [sweep_run(bundles[0], grid),
                     sweep_run(bundles[1], grid)])


def test_compiled_bundles_and_chunking(bundles, grid):
    """Pre-compiled bundles pass straight through; scenario chunking of the
    super-bundle stays bit-identical."""
    cbs = [compile_bundle(b) for b in bundles]
    multi = sweep_run_many(cbs, grid)
    chunked = sweep_run_many(cbs, grid, chunk_scenarios=2)
    for rm, rc in zip(multi, chunked):
        np.testing.assert_array_equal(rm.gain_ns, rc.gain_ns)
    assert multi[0].compiled is cbs[0]        # per-bundle result keeps its cb


def test_categorical_transfer_axes(bundles):
    g = ParamGrid.product(ModelParams.multinode(),
                          cxl_lat_ns=[250.0, 500.0],
                          mpi_transfer=["hockney", "loggp"])
    singles = [sweep_run(b, g) for b in bundles]
    _assert_matches(sweep_run_many(bundles, g), singles, "categorical")


def test_concat_bundles_layout(bundles):
    cbs = [compile_bundle(b) for b in bundles]
    sup = concat_bundles(cbs)
    assert sup.n_calls == sum(cb.n_calls for cb in cbs)
    assert sup.call_ids == tuple(c for cb in cbs for c in cb.call_ids)
    # per-call counter arrays repeat each bundle's scalar over its calls
    assert sup.counters.wall_time_ns.shape == (sup.n_calls,)
    lo = 0
    for cb in cbs:
        hi = lo + cb.n_calls
        np.testing.assert_array_equal(
            sup.counters.wall_time_ns[lo:hi],
            np.full(cb.n_calls, cb.counters.wall_time_ns))
        np.testing.assert_array_equal(
            sup.sampling_period[lo:hi],
            np.full(cb.n_calls, cb.sampling_period))
        lo = hi
    # segment ids are offset by the running call count
    assert int(sup.hit_seg.max()) < sup.n_calls
    with pytest.raises(ValueError):
        concat_bundles([])


def test_names_validation(bundles, grid):
    with pytest.raises(ValueError):
        sweep_run_many(bundles, grid, names=["a"])     # 1 name, 3 bundles
    multi = sweep_run_many(bundles, grid)
    assert multi.names == ("bundle0", "bundle1", "bundle2")


def test_deployment_aggregates(bundles, grid):
    multi = sweep_run_many(bundles, grid,
                           names=["prefill", "decode", "embed"])
    # unweighted: Σ baseline / Σ predicted
    base = sum(r.compiled.baseline_runtime_ns for r in multi)
    runt = sum(r.predicted_runtime_ns() for r in multi)
    np.testing.assert_allclose(multi.predicted_speedup(), base / runt)
    # dict weights (a decode-heavy deployment) reweight the mix
    w = {"prefill": 1.0, "decode": 128.0, "embed": 1.0}
    base_w = sum(w[n] * r.compiled.baseline_runtime_ns
                 for n, r in zip(multi.names, multi))
    runt_w = sum(w[n] * r.predicted_runtime_ns()
                 for n, r in zip(multi.names, multi))
    np.testing.assert_allclose(multi.predicted_speedup(weights=w),
                               base_w / runt_w)
    assert 0 <= multi.best_scenario() < len(grid)
    rows = multi.summary_rows()
    assert len(rows) == len(grid)
    assert "speedup[decode]" in rows[0] and "predicted_speedup" in rows[0]
    with pytest.raises(ValueError):
        multi.predicted_speedup(weights=[1.0])         # wrong length


def test_step_weights_object_as_weights(bundles, grid):
    """Anything with step_weights() — a serve engine, its stats — can be
    passed straight to weights=: the OBSERVED step mix prices the
    deployment (unknown step names default to 1.0)."""
    multi = sweep_run_many(bundles, grid,
                           names=["prefill", "decode", "embed"])

    class FakeEngine:
        def step_weights(self):
            return {"prefill": 1.0, "decode": 128.0, "embed": 1.0,
                    "prefill_chunk@16": 7.0}           # extra key ignored

    w = {"prefill": 1.0, "decode": 128.0, "embed": 1.0}
    np.testing.assert_array_equal(
        multi.predicted_speedup(weights=FakeEngine()),
        multi.predicted_speedup(weights=w))


SYNTH_HLO_A = """
HloModule syntha

ENTRY %main (p0: bf16[1024,1024]) -> bf16[1024,1024] {
  %p0 = bf16[1024,1024]{1,0} parameter(0)
  %ar = bf16[1024,1024]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %out = bf16[1024,1024]{1,0} add(%ar, %ar)
}
"""

SYNTH_HLO_B = """
HloModule synthb

ENTRY %main (p0: bf16[512,512]) -> bf16[1024,512] {
  %p0 = bf16[512,512]{1,0} parameter(0)
  %ag = bf16[1024,512]{1,0} all-gather(%p0), replica_groups={{0,1}}, dimensions={0}
  ROOT %out = bf16[1024,512]{1,0} add(%ag, %ag)
}
"""


@pytest.mark.parametrize("backend", BACKENDS)
def test_advisor_sweep_text_many(backend):
    """The advisor's batched deployment sweep: every step's collectives
    priced under one grid, per-step results equal to per-step sweeps."""
    adv = CommAdvisor()
    grid = adv.default_grid(3, 2)
    texts = {"prefill": SYNTH_HLO_A, "decode": SYNTH_HLO_B}
    multi = adv.sweep_text_many(texts, grid, backend=backend)
    assert multi.names == ("prefill", "decode")
    _assert_matches(multi,
                    [adv.sweep_text(SYNTH_HLO_A, grid, backend=backend),
                     adv.sweep_text(SYNTH_HLO_B, grid, backend=backend)],
                    backend)
    assert multi["decode"].compiled.n_calls == 1
    rows = multi.summary_rows(weights={"decode": 64.0})
    assert len(rows) == len(grid)


def test_advisor_sweep_text_many_costs_alignment():
    adv = CommAdvisor()
    grid = adv.default_grid(2, 2)
    # explicit names reorder a texts dict (costs keyed by name follow)
    multi = adv.sweep_text_many({"a": SYNTH_HLO_A, "b": SYNTH_HLO_B}, grid,
                                names=("b", "a"))
    assert multi.names == ("b", "a")
    assert multi["a"].call_ids == adv.sweep_text(SYNTH_HLO_A, grid).call_ids
    with pytest.raises(ValueError):            # dict costs need named steps
        adv.sweep_text_many([SYNTH_HLO_A], grid, costs={"a": {}})


@pytest.fixture
def packs(monkeypatch):
    """Start from an empty super-bundle cache and record, per priced
    list, whether its super-bundle was reused (``"reused"``) and, per
    executor call, whether it built a new jit (``"jit_miss"``)."""
    from repro.core import sweep, sweep_kernel
    seen = {"reused": [], "jit_miss": []}
    super_bundle, run_jitted = sweep._super_bundle, sweep_kernel._run_jitted

    def spy_super(cbs):
        out = super_bundle(cbs)
        seen["reused"].append(int(out[1]))
        return out

    def spy_run(fn, miss, x64, *args):
        seen["jit_miss"].append(int(miss))
        return run_jitted(fn, miss, x64, *args)

    monkeypatch.setattr(sweep, "_super_bundle", spy_super)
    monkeypatch.setattr(sweep_kernel, "_run_jitted", spy_run)
    sweep._SUPER_BUNDLES.clear()
    yield seen
    sweep._SUPER_BUNDLES.clear()


@pytest.mark.parametrize("backend", ("jax", "pallas"))
def test_equal_bundles_reuse_the_packed_plan(bundles, grid, packs, backend):
    """Bundles compiled afresh, equal in content, reuse the super-bundle
    and its jit: no new jit and no retrace, and results bit-identical to
    a run that starts from an empty cache."""
    from repro.core.sweep import _SUPER_BUNDLES
    price([compile_bundle(b) for b in bundles], grid, plan=backend)
    (super_cb,) = _SUPER_BUNDLES.values()
    again = price([compile_bundle(b) for b in bundles], grid, plan=backend)
    assert packs == {"reused": [0, 1], "jit_miss": [1, 0]}
    assert list(_SUPER_BUNDLES.values()) == [super_cb]
    (fn,) = super_cb._jit_cache.values()
    assert fn._cache_size() == 1
    _SUPER_BUNDLES.clear()
    cold = price(bundles, grid, plan=backend)
    assert packs == {"reused": [0, 1, 0], "jit_miss": [1, 0, 1]}
    for rw, rc in zip(again, cold):
        for f in MATRIX_FIELDS:
            np.testing.assert_array_equal(getattr(rw, f), getattr(rc, f))


def test_one_changed_weight_is_a_new_plan(bundles, grid, packs):
    """A bundle whose one sample weight differs has another fingerprint:
    its list packs anew and misses the jit, and the changed bundle prices
    as its own ``sweep_run`` does, not as the bundle it was built from."""
    import dataclasses
    cbs = [compile_bundle(b) for b in bundles]
    price(cbs, grid, plan="jax")
    changed = make_bundle(1, 2, 900.0, 2.5e9)
    samples = changed.call("b1_recv1").samples
    samples[-1] = dataclasses.replace(samples[-1],
                                      weight=2.0 * samples[-1].weight)
    ccb = compile_bundle(changed)
    assert ccb.fingerprint() != cbs[1].fingerprint()
    multi = price([cbs[0], ccb, cbs[2]], grid, plan="jax")
    assert packs == {"reused": [0, 0], "jit_miss": [1, 1]}
    _assert_matches(multi, [sweep_run(b, grid, plan=ExecPlan("jax"))
                            for b in (cbs[0], ccb, cbs[2])], "changed")
    old = sweep_run(cbs[1], grid, plan=ExecPlan("jax"))
    assert any(not np.array_equal(getattr(multi[1], f), getattr(old, f))
               for f in MATRIX_FIELDS)


def test_another_order_is_another_plan(bundles, grid, packs):
    """The key is ordered: the same bundles reversed pack anew, and each
    bundle's result is still its own."""
    cbs = [compile_bundle(b) for b in bundles]
    price(cbs, grid, plan="jax")
    multi = price(cbs[::-1], grid, plan="jax")
    assert packs == {"reused": [0, 0], "jit_miss": [1, 1]}
    _assert_matches(multi, [sweep_run(b, grid, plan=ExecPlan("jax"))
                            for b in cbs[::-1]], "reversed")


def test_super_bundle_cache_is_bounded(bundles, grid, packs):
    """After more distinct lists than the cache holds, the first list's
    super-bundle is gone and it packs anew; a recent one is still kept."""
    from repro.core.sweep import _SUPER_BUNDLES, _SUPER_BUNDLES_MAX
    cbs = [compile_bundle(b) for b in bundles]
    lists = [list(p) for n in (3, 2, 1)
             for p in itertools.permutations(cbs, n)]
    assert len(lists) > _SUPER_BUNDLES_MAX + 1
    lists = lists[:_SUPER_BUNDLES_MAX + 1]
    for cb_list in lists:
        price(cb_list, grid)
    assert len(_SUPER_BUNDLES) == _SUPER_BUNDLES_MAX
    price(lists[-1], grid)
    price(lists[0], grid)
    assert packs["reused"] == [0] * len(lists) + [1, 0]
    assert len(_SUPER_BUNDLES) == _SUPER_BUNDLES_MAX
