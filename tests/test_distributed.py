"""Multi-device distribution tests.

These spawn subprocesses with ``XLA_FLAGS=--xla_force_host_platform_device_
count=N`` because the flag must be set before jax initializes — the main
pytest process keeps the default single device (per the dry-run contract).
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_stencil_backends_match_reference():
    """message_based (ppermute) == message_free (shared window) == oracle,
    on a real 2x2 process grid."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.comm.topology import grid_mesh
        from repro.apps.stencil.jax_impl import (init_plane, make_runner,
                                                 reference_step)
        mesh = grid_mesh(2, 2)
        plane = init_plane(32, 32)
        ref = plane
        for _ in range(5):
            ref = reference_step(ref)
        for backend in ("message_based", "message_free"):
            run = make_runner(mesh, backend)
            out = run(plane, 5)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-6, rtol=1e-6)
        print("stencil backends OK")
    """, n=4)


def test_hpcg_cg_converges_distributed():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh
        from repro.apps.hpcg.jax_impl import make_cg, make_problem
        mesh = make_mesh((4,), ("z",))
        b = make_problem((16, 16, 16))
        for backend in ("message_based", "message_free"):
            cg = make_cg(mesh, backend, n_iter=30)
            x, res = cg(b, jnp.zeros_like(b))
            err = float(jnp.max(jnp.abs(x - 1.0)))
            assert err < 1e-2, (backend, err)
        print("hpcg OK")
    """, n=4)


def test_message_free_window_matches_ppermute_oracle():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.comm import message_based, message_free
        from repro.compat import shard_map
        mesh = make_mesh((4,), ("z",))
        x = jnp.arange(4 * 6 * 5.0).reshape(4 * 6, 5)

        def body(comm, block):
            lo, hi = comm.exchange_planes_1d(block, "z")
            return jnp.concatenate([lo, hi], axis=0)

        outs = []
        for comm in (message_based, message_free):
            f = jax.jit(shard_map(partial(body, comm), mesh=mesh,
                                  in_specs=P("z"), out_specs=P("z")))
            outs.append(np.asarray(f(x)))
        np.testing.assert_allclose(outs[0], outs[1])
        print("window == ppermute OK")
    """, n=4)


def test_elastic_checkpoint_restore_across_meshes(tmp_path):
    """Save sharded on a (1,4) mesh; restore onto (2,2) — elastic restart."""
    run_with_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh
        from repro.configs import ARCHS
        from repro.models.factory import make_model
        from repro.parallel import param_pspecs, named
        from repro.train import checkpoint as ckpt
        cfg = ARCHS["qwen2.5-3b"].reduced()
        model = make_model(cfg)
        mesh1 = make_mesh((1, 4), ("data", "model"))
        with mesh1:
            params = jax.jit(model.init, out_shardings=named(
                mesh1, param_pspecs(model.init(jax.random.PRNGKey(0))))
                )(jax.random.PRNGKey(0))
        ckpt.save({str(tmp_path)!r}, 3, params)
        mesh2 = make_mesh((2, 2), ("data", "model"))
        shards = named(mesh2, param_pspecs(params))
        restored, _ = ckpt.restore({str(tmp_path)!r}, 3,
                                   jax.eval_shape(lambda: params), shards)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        print("elastic restore OK")
    """, n=4)


def test_sharded_train_step_runs():
    """A real sharded train step on a (2,2) mesh produces finite loss and
    keeps param shardings."""
    run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.compat import make_mesh
        from repro.configs import ARCHS
        from repro.models.config import ShapeConfig
        from repro.models.factory import make_inputs, make_model
        from repro.parallel import (batch_pspecs, named, param_pspecs,
                                    zero1_pspecs)
        from repro.train.loop import make_train_step
        from repro.train.optimizer import AdamWConfig, adamw_init
        from jax.sharding import PartitionSpec as P
        cfg = ARCHS["qwen2.5-3b"].reduced()
        shape = ShapeConfig("t", "train", 64, 4)
        mesh = make_mesh((2, 2), ("data", "model"))
        model = make_model(cfg, moe_impl="dense",
                           act_pspec=P(("data",), None, None))
        with mesh:
            params = model.init(jax.random.PRNGKey(0))
            pspecs = param_pspecs(params)
            pshard = named(mesh, pspecs)
            oshard = named(mesh, {"mu": zero1_pspecs(params, pspecs, mesh),
                                  "nu": zero1_pspecs(params, pspecs, mesh),
                                  "count": P()})
            batch = make_inputs(cfg, shape, abstract=False)
            bshard = named(mesh, batch_pspecs(batch, mesh))
            step = jax.jit(make_train_step(model.loss, AdamWConfig(),
                                           n_micro=2, grad_shardings=pshard),
                           in_shardings=(pshard, oshard, bshard),
                           out_shardings=(pshard, oshard, None))
            opt = jax.jit(adamw_init, out_shardings=oshard)(params)
            p2, o2, m = step(params, opt, batch)
            assert jnp.isfinite(m.loss), m
        print("sharded step OK, loss", float(m.loss))
    """, n=4)


def test_ep_local_moe_matches_dense_on_mesh():
    """EP-local MoE == dense dispatch on a real 2x4 mesh (no-drop capacity)."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh
        from repro.configs import ARCHS
        from repro.models.factory import make_model, make_inputs
        from repro.models.config import ShapeConfig
        from repro.parallel import param_pspecs, named
        cfg = ARCHS["phi3.5-moe-42b-a6.6b"].reduced().replace(
            capacity_factor=8.0)
        batch = make_inputs(cfg, ShapeConfig("t", "train", 64, 2),
                            abstract=False)
        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh:
            params = make_model(cfg).init(jax.random.PRNGKey(0))
            params = jax.device_put(params, named(mesh, param_pspecs(params)))
            ld, _ = jax.jit(make_model(cfg, moe_impl="dense").forward)(
                params, batch)
            le, _ = jax.jit(make_model(cfg, moe_impl="ep_local").forward)(
                params, batch)
            g = jax.jit(jax.grad(make_model(cfg, moe_impl="ep_local").loss))(
                params, batch)
        np.testing.assert_allclose(np.asarray(ld, np.float32),
                                   np.asarray(le, np.float32),
                                   atol=1e-3, rtol=1e-3)
        assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))
        print("ep_local == dense on mesh OK")
    """, n=8)


def test_pipeline_parallel_matches_sequential():
    """GPipe schedule over the pod axis == sequential stack, forward AND
    backward (autodiff through the wavefront)."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.compat import shard_map
        from repro.parallel.pipeline import pipeline_apply
        L, D, M, B = 4, 16, 6, 3
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (L, D, D)) * 0.3
        xs = jax.random.normal(jax.random.fold_in(key, 1), (M, B, D))
        def block_fn(w_stack, x):
            def body(c, w):
                return jnp.tanh(c @ w), None
            out, _ = jax.lax.scan(body, x, w_stack)
            return out
        ref = jax.vmap(lambda x: block_fn(ws, x))(xs)
        mesh = make_mesh((2, 2), ("pod", "data"))
        f = shard_map(
            lambda w, x: pipeline_apply(w, x, block_fn, axis="pod"),
            mesh=mesh, in_specs=(P("pod"), P()), out_specs=P(),
            axis_names={"pod"}, check_vma=False)
        with mesh:
            out = jax.jit(f)(ws, xs)
            g_pp = jax.jit(jax.grad(
                lambda w, x: jnp.sum(f(w, x) ** 2)))(ws, xs)
        g_ref = jax.grad(
            lambda w, x: jnp.sum(jax.vmap(
                lambda xi: block_fn(w, xi))(x) ** 2))(ws, xs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_ref),
                                   atol=1e-4, rtol=1e-4)
        print("pipeline OK")
    """, n=4)


@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_distributed_sweep_uneven_shards_match_numpy(n_dev):
    """S=37 with chunk=10 never divides evenly: every chunk exercises the
    pad-and-mask path, and edge-padded rows must not leak into the top-k
    or the exact aggregates on ANY device count."""
    run_with_devices(f"""
        import sys
        sys.path.insert(0, {os.path.join(ROOT, "tests")!r})
        import jax
        import numpy as np
        from test_sweep_backends import small_bundle
        from repro.core import (ExecPlan, ModelParams, SweepAggregates,
                                adaptive_sample, compile_bundle, price)
        assert jax.device_count() == {n_dev}
        cb = compile_bundle(small_bundle())
        g = adaptive_sample(ModelParams.multinode(), 37, seed=4,
                            mpi_transfer=["hockney", "loggp"],
                            cxl_lat_ns=(250.0, 700.0))
        res = price(cb, g, plan=ExecPlan.parse(
            "distributed:topk=9,chunk=10,devices={n_dev}"))
        ref = price(cb, g)
        sp = ref.predicted_speedup()
        assert np.array_equal(np.sort(res.indices), np.sort(ref.topk(9)))
        np.testing.assert_allclose(res.speedups, sp[res.indices], rtol=1e-9)
        np.testing.assert_allclose(res.result.gain_ns,
                                   ref.gain_ns[res.indices], rtol=1e-9)
        ragg = SweepAggregates.from_result(ref)
        agg = res.aggregates
        assert agg.count == 37
        assert np.array_equal(agg.hist, ragg.hist)
        assert np.array_equal(agg.n_beneficial, ragg.n_beneficial)
        np.testing.assert_allclose(
            [agg.speedup_mean, agg.speedup_min, agg.speedup_max],
            [ragg.speedup_mean, ragg.speedup_min, ragg.speedup_max],
            rtol=1e-9)
        np.testing.assert_allclose(agg.gain_sum, ragg.gain_sum, rtol=1e-9)
        print("uneven shards OK")
    """, n=n_dev)


def test_distributed_million_scenario_adaptive_sweep():
    """A 1M-scenario adaptive sweep (500k LHS seed + one refinement round)
    on 4 emulated devices: completes, keeps exact aggregates over every
    scenario, and never materializes more than one chunk shard per device
    — the peak per-shard allocation is pinned."""
    run_with_devices(f"""
        import sys
        sys.path.insert(0, {os.path.join(ROOT, "tests")!r})
        import numpy as np
        from test_sweep_backends import small_bundle
        from repro.compat import padded_size
        from repro.core import (ExecPlan, ModelParams, adaptive_sample,
                                compile_bundle, price)
        from repro.core.sweep_kernel import DIST_CHUNK_DEFAULT
        cb = compile_bundle(small_bundle())
        S = 500_000
        g = adaptive_sample(ModelParams.multinode(), S, seed=1,
                            mpi_transfer=["hockney", "loggp"],
                            cxl_lat_ns=(250.0, 700.0),
                            cxl_atomic_lat_ns=(300.0, 800.0))
        res = price(cb, g, plan=ExecPlan.parse(
            "distributed:devices=4,topk=64,refine=1"))
        assert len(res.scenarios) == 2 * S       # 1M scenarios evaluated
        assert res.aggregates.count == 2 * S
        assert len(res) == 64
        assert list(res.speedups) == sorted(res.speedups, reverse=True)
        # streaming bound: per-device working set is one chunk shard, a
        # tiny fraction of the full scenario axis
        assert res.shard_rows == padded_size(DIST_CHUNK_DEFAULT, 4) // 4
        assert res.shard_rows * 4 <= DIST_CHUNK_DEFAULT < (2 * S) // 7
        # refinement samples stayed inside the recorded ranges
        lab = res.scenarios.label_at(int(res.indices[0]))
        assert 250.0 <= lab["cxl_lat_ns"] <= 700.0
        assert 300.0 <= lab["cxl_atomic_lat_ns"] <= 800.0
        print("1M adaptive OK shard_rows", res.shard_rows)
    """, n=4, timeout=900)


def test_compressed_psum_error_feedback():
    """int8 compressed all-reduce: per-step error bounded by the quant
    step; error feedback keeps the RUNNING SUM unbiased over steps."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.compat import shard_map
        from repro.parallel.pipeline import compressed_psum
        mesh = make_mesh((4,), ("dp",))
        xs = jax.random.normal(jax.random.PRNGKey(0), (5, 4, 64))  # 5 steps

        def steps(xs):
            def body(res, x):
                out, res = compressed_psum(x, "dp", res)
                return res, out
            res0 = jnp.zeros_like(xs[0], jnp.float32)
            _, outs = jax.lax.scan(body, res0, xs)
            return outs

        f = jax.jit(shard_map(steps, mesh=mesh, in_specs=P(None, "dp"),
                              out_specs=P(None, "dp")))
        with mesh:
            outs = np.asarray(f(xs))
        exact = np.asarray(jnp.sum(xs, axis=1, keepdims=True))
        exact = np.broadcast_to(exact, outs.shape)
        # per-step error small; cumulative-sum error does not grow (EF)
        step_err = np.abs(outs - exact).max()
        cum_err = np.abs(outs.cumsum(0) - exact.cumsum(0)).max()
        assert step_err < 0.2, step_err
        assert cum_err < 0.2, cum_err
        print("compressed psum OK", step_err, cum_err)
    """, n=4)
