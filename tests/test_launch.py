"""Launch-path regression tests: build_step lowers+compiles for every
shape kind on a small production-like mesh (subprocess: needs 8 host
devices before jax init).  Catches sharding-rule regressions without the
full dry-run."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8, timeout: int = 1200):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "phi3.5-moe-42b-a6.6b",
                                  "jamba-v0.1-52b", "falcon-mamba-7b",
                                  "internvl2-2b", "musicgen-medium"])
def test_build_step_compiles_all_kinds(arch):
    run_with_devices(f"""
        import jax
        from repro.compat import make_mesh
        from repro.configs import ARCHS
        from repro.models.config import ShapeConfig
        from repro.launch.dryrun import build_step
        cfg = ARCHS[{arch!r}].reduced()
        mesh = make_mesh((2, 4), ("data", "model"))
        shapes = [ShapeConfig("t", "train", 64, 8),
                  ShapeConfig("p", "prefill", 64, 8),
                  ShapeConfig("d", "decode", 64, 8)]
        for shape in shapes:
            with mesh:
                fn, args, meta = build_step(cfg, shape, mesh)
                compiled = fn.lower(*args).compile()
                assert compiled.cost_analysis() is not None
        print("build_step OK for", {arch!r})
    """)


def test_dryrun_cell_record_schema():
    """run_cell emits the full record schema the benchmarks consume."""
    out = run_with_devices("""
        import jax, json
        from repro.compat import make_mesh
        from repro.configs import ARCHS
        from repro.models.config import ShapeConfig
        from repro.launch.dryrun import run_cell
        cfg = ARCHS["qwen2.5-3b"].reduced()
        mesh = make_mesh((2, 4), ("data", "model"))
        rec = run_cell(cfg, ShapeConfig("train_4k", "train", 64, 8), mesh)
        for key in ("roofline", "memory", "collectives", "analytic",
                    "cost_raw", "compile_s"):
            assert key in rec, key
        for key in ("compute_s", "memory_s", "collective_s", "dominant",
                    "useful_flops_ratio"):
            assert key in rec["roofline"], key
        assert "fits_hbm" in rec["memory"]
        print("record schema OK")
    """)
    assert "record schema OK" in out


def test_mesh_seam_builds_auto_axes():
    """Every mesh comes from the one seam with Auto axis types: under
    jax.make_mesh's Explicit default, the vocab-sharded embedding gather
    is a sharding type error."""
    import jax
    from jax.sharding import AxisType

    from repro.compat import device_mesh_1d, make_mesh
    from repro.launch.mesh import make_mesh as launch_make_mesh
    for mesh in (make_mesh((1, 1), ("data", "model")),
                 make_mesh((1,), ("z",), devices=jax.devices()),
                 launch_make_mesh((1, 1), ("data", "model")),
                 device_mesh_1d(1)):
        assert set(mesh.axis_types) == {AxisType.Auto}
    with pytest.raises(ValueError, match="needs 2 devices"):
        make_mesh((2,), ("z",), devices=jax.devices()[:1])
