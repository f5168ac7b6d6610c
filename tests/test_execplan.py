"""ExecPlan + backend-registry tests: the frozen execution-config object,
its CLI string form (``ExecPlan.parse`` — the single source of the
unknown-backend usage message), and ``register_backend`` as the open
replacement for the old if/elif backend dispatch."""
import numpy as np
import pytest

from repro.core import (CommRecord, CounterSet, DataSource, ExecPlan,
                        LoadSample, ModelParams, ParamGrid, TraceBundle,
                        compile_bundle, known_backends, price,
                        register_backend)
from repro.core.execplan import _BACKENDS, resolve_backend
from repro.core.sweep_kernel import price_grid_numpy


def small_bundle(n_sites: int = 2) -> TraceBundle:
    rng = np.random.default_rng(11)
    b = TraceBundle(sampling_period=500.0)
    b.counters = CounterSet(ld_ins=5e9, l1_ldm=6e8, l3_ldm=9e7,
                            tot_cyc=3.1e9, imc_reads=2.2e8,
                            wall_time_ns=1.5e9)
    sources = list(DataSource)
    for i in range(n_sites):
        cid = f"recv_{i}"
        for k in range(8):
            b.add_sample(LoadSample(
                call_id=cid, lat_ns=float(rng.uniform(5, 400)),
                source=sources[(i + k) % len(sources)],
                weight=float(rng.uniform(0.5, 3.0))))
        b.add_comm(CommRecord(call_id=cid, bytes=1024 * (i + 1), count=2))
    return b


@pytest.fixture(scope="module")
def cb():
    return compile_bundle(small_bundle())


@pytest.fixture(scope="module")
def grid():
    return ParamGrid.product(ModelParams.multinode(),
                             cxl_lat_ns=[250.0, 400.0],
                             cxl_atomic_lat_ns=[350.0, 653.0])


# ----------------------------------------------------------------- ExecPlan

def test_defaults():
    p = ExecPlan()
    assert (p.backend, p.chunk_scenarios, p.vmap_scenarios,
            p.pallas_interpret, p.x64) == ("numpy", None, False, None, None)
    # unset precision and mode resolve from the platform (CPU here)
    assert p.resolved().x64 is True
    r = ExecPlan(backend="pallas").resolved()
    assert (r.pallas_interpret, r.x64) == (True, True)


def test_pallas_plan_resolves_from_backend(monkeypatch):
    """The Pallas plan follows ``jax.default_backend()``: compiled in
    float32 on TPU, interpreted in float64 elsewhere; explicit choices
    win, and a compiled float64 kernel (which Mosaic cannot build) is
    refused up front."""
    import jax
    assert ExecPlan(backend="pallas").resolved().pallas_interpret is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    r = ExecPlan(backend="pallas").resolved()
    assert (r.pallas_interpret, r.x64) == (False, False)
    assert ExecPlan(backend="jax").resolved().x64 is True
    r = ExecPlan(backend="pallas", pallas_interpret=True).resolved()
    assert (r.pallas_interpret, r.x64) == (True, True)
    with pytest.raises(ValueError, match="no float64"):
        ExecPlan(backend="pallas", x64=True).resolved()


def test_validation():
    with pytest.raises(ValueError):
        ExecPlan(chunk_scenarios=0)
    with pytest.raises(ValueError):
        ExecPlan(vmap_scenarios=True)              # numpy backend
    with pytest.raises(ValueError):
        ExecPlan(backend="pallas", vmap_scenarios=True)
    ExecPlan(backend="jax", vmap_scenarios=True)   # fine


def test_replace():
    p = ExecPlan(backend="jax").replace(chunk_scenarios=4)
    assert p.backend == "jax" and p.chunk_scenarios == 4


def test_unknown_backend_resolves_lazily(cb, grid):
    """An ExecPlan may NAME a backend registered later; resolution (and
    the canonical error) happens at price time."""
    plan = ExecPlan(backend="not_yet_registered")   # constructing is fine
    with pytest.raises(ValueError, match="unknown backend"):
        price(cb, grid, plan=plan)


def test_executor_returns_registered_fn():
    assert ExecPlan().executor() is _BACKENDS["numpy"]
    with pytest.raises(ValueError):
        ExecPlan(backend="nope").executor()


# -------------------------------------------------------------------- parse

def test_parse_bare_backend():
    for name in known_backends():
        assert ExecPlan.parse(name) == ExecPlan(backend=name)


def test_parse_options():
    p = ExecPlan.parse("numpy:chunk=8")
    assert p == ExecPlan(chunk_scenarios=8)
    p = ExecPlan.parse("pallas:interpret=0,chunk=4")
    assert p == ExecPlan(backend="pallas", pallas_interpret=False,
                         chunk_scenarios=4)
    p = ExecPlan.parse("jax:vmap=1,x64=false")
    assert p == ExecPlan(backend="jax", vmap_scenarios=True, x64=False)
    assert ExecPlan.parse("jax:vmap").vmap_scenarios   # bare flag = true


def test_parse_overrides():
    p = ExecPlan.parse("jax", chunk_scenarios=3)
    assert p == ExecPlan(backend="jax", chunk_scenarios=3)
    # None overrides mean "not specified": a CLI forwarding its flag
    # default must not clobber a spec-supplied option
    p = ExecPlan.parse("numpy:chunk=8", chunk_scenarios=None)
    assert p.chunk_scenarios == 8


def test_parse_unknown_backend_usage_message():
    """The one canonical usage error every CLI surfaces verbatim: it must
    name the offender AND list what IS registered."""
    with pytest.raises(ValueError) as e:
        ExecPlan.parse("tpu_magic")
    msg = str(e.value)
    assert "unknown backend 'tpu_magic'" in msg
    assert "registered:" in msg
    for name in ("numpy", "jax", "pallas"):
        assert name in msg


def test_parse_unknown_option():
    with pytest.raises(ValueError, match="unknown ExecPlan option"):
        ExecPlan.parse("jax:warp_speed=9")


def test_parse_invalid_combo_still_validates():
    with pytest.raises(ValueError, match="vmap_scenarios requires"):
        ExecPlan.parse("numpy:vmap=1")


# ----------------------------------------------------------------- registry

def test_builtins_registered():
    assert set(known_backends()) >= {"numpy", "jax", "pallas"}


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        register_backend("numpy", lambda cb, v, plan: None)


def test_register_custom_backend_runs_through_price(cb, grid):
    calls = []

    def traced(cb_, view, plan):
        calls.append(plan)
        return price_grid_numpy(cb_, view)

    register_backend("traced_numpy", traced)
    try:
        plan = ExecPlan(backend="traced_numpy", chunk_scenarios=1)
        res = price(cb, grid, plan=plan)
        ref = price(cb, grid)
        np.testing.assert_array_equal(res.gain_ns, ref.gain_ns)
        # chunking wraps ANY registered backend: one call per scenario,
        # each handed the active plan, resolved for the platform
        assert len(calls) == len(grid)
        assert all(p == plan.resolved() for p in calls)
        assert res.plan == plan.resolved()
        # parse sees it too — the registry is the single source of truth
        assert "traced_numpy" in known_backends()
        assert ExecPlan.parse("traced_numpy").backend == "traced_numpy"
    finally:
        _BACKENDS.pop("traced_numpy", None)


def test_overwrite_registration():
    def fn(cb, v, plan):                            # pragma: no cover
        raise AssertionError
    register_backend("tmp_backend", fn)
    try:
        fn2 = register_backend("tmp_backend", lambda cb, v, plan: {},
                               overwrite=True)
        assert resolve_backend("tmp_backend") is fn2
    finally:
        _BACKENDS.pop("tmp_backend", None)


def test_x64_false_plan_runs(cb, grid):
    """The f32 accelerator-speed mode executes and stays in the right
    ballpark of the f64 reference (loose bound — it IS single precision)."""
    ref = price(cb, grid, plan=ExecPlan("jax"))
    f32 = price(cb, grid, plan=ExecPlan("jax", x64=False))
    err = np.max(np.abs(f32.gain_ns - ref.gain_ns)
                 / np.maximum(np.abs(ref.gain_ns), 1.0))
    assert err < 1e-2
    import jax.numpy as jnp                    # never leaks global x64
    assert jnp.asarray(1.0).dtype == jnp.float32


def test_parse_rejects_duplicate_option():
    with pytest.raises(ValueError, match="duplicate option 'chunk'"):
        ExecPlan.parse("pallas:chunk=4,chunk=8")
    with pytest.raises(ValueError, match="duplicate option 'x64'"):
        ExecPlan.parse("jax:x64=1,chunk=2,x64=0")


def test_parse_rejects_empty_option_segment():
    for spec in ("jax:", "pallas:chunk=4,,x64=1", "numpy: ,chunk=2",
                 "jax:chunk=2,"):
        with pytest.raises(ValueError, match="empty option segment"):
            ExecPlan.parse(spec)
    # a bare backend name (no colon at all) is still fine
    assert ExecPlan.parse("jax").backend == "jax"


def test_to_string_roundtrips_every_plan():
    plans = [
        ExecPlan(),
        ExecPlan(backend="jax"),
        ExecPlan(backend="jax", vmap_scenarios=True, x64=False),
        ExecPlan(backend="pallas", pallas_interpret=False,
                 chunk_scenarios=8),
        ExecPlan(backend="numpy", chunk_scenarios=64),
        ExecPlan(backend="distributed", devices=4, topk=16, refine=2),
        ExecPlan(backend="distributed", topk=1),
    ]
    for p in plans:
        assert ExecPlan.parse(p.to_string()) == p, p.to_string()


def test_to_string_emits_only_non_defaults():
    assert ExecPlan().to_string() == "numpy"
    assert ExecPlan(backend="jax").to_string() == "jax"
    assert ExecPlan(backend="pallas", pallas_interpret=False).to_string() \
        == "pallas:interpret=0"
    assert ExecPlan(backend="distributed", devices=8, topk=64,
                    refine=3).to_string() == "distributed:devices=8,refine=3"


def test_parse_streaming_options_and_validation():
    p = ExecPlan.parse("distributed:devices=8,topk=64,refine=3")
    assert (p.devices, p.topk, p.refine) == (8, 64, 3)
    with pytest.raises(ValueError, match="devices must be >= 1"):
        ExecPlan(devices=0)
    with pytest.raises(ValueError, match="topk must be >= 1"):
        ExecPlan(topk=0)
    with pytest.raises(ValueError, match="refine must be >= 0"):
        ExecPlan(refine=-1)


def test_streaming_registry_flags():
    from repro.core import is_streaming
    assert is_streaming("distributed")
    for name in ("numpy", "jax", "pallas"):
        assert not is_streaming(name)
