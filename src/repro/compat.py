"""Compatibility seam for the JAX API surface this repo depends on.

Supported stack: JAX 0.9.0 (jaxlib 0.9.0, libtpu 0.0.34) on Python 3.12.

Policy: when a JAX symbol moves or changes shape between releases, it gets
ONE adapter here and every call site imports it from ``repro.compat`` —
never from the drifting location directly.  That keeps version knowledge
in a single file.

The policy is machine-enforced: the ``compat-drift`` rule of
``python -m repro.lint`` (see :mod:`repro.analysis.lint` and the README's
"Static analysis" section) flags any import or attribute use of the
drifting symbols below outside this file — this module is the one
allowlisted home, and ``jax.experimental.pallas`` is additionally allowed
inside ``kernels/``.

Current shims:
  * ``shard_map`` / ``axis_size`` / ``segment_sum`` — re-exported from
    their JAX homes so a future relocation is a one-line fix.
  * ``normalize_cost_analysis`` — ``Compiled.cost_analysis()`` as a dict,
    never an exception (some backends return ``None`` or raise).
  * ``enable_x64`` — scoped double precision (``jax.enable_x64(True)``)
    for the sweep kernel's jax backend; the process-global flag is never
    flipped.
  * ``make_mesh`` / ``device_mesh_1d`` — device-mesh construction with
    ``Auto`` axis types.  ``jax.make_mesh`` defaults to ``Explicit`` axes,
    under which an un-annotated gather over a sharded table (the
    embedding lookup) is a type error; the model code relies on GSPMD
    propagation, so every mesh is built here.  The ``compat-drift`` lint
    rule flags ``Mesh``/``make_mesh`` construction anywhere but here and
    ``launch/mesh.py``, so ALL mesh plumbing stays behind this seam.
  * ``mesh_in_context`` — whether a mesh is in context, either through
    ``jax.set_mesh`` or the ``with mesh:`` form the train driver uses.
  * ``pad_to_multiple`` / ``padded_size`` — uneven-shard padding for the
    scenario-axis ``shard_map`` executors (a scenario count that does not
    divide the device count is edge-padded and masked).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.ops import segment_sum  # noqa: F401  (re-export)

shard_map = jax.shard_map
axis_size = jax.lax.axis_size


def enable_x64():
    """Context manager: double precision inside the ``with`` block only."""
    return jax.enable_x64(True)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """A ``jax.sharding.Mesh`` over ``axis_shapes`` with ``Auto`` axes.

    Without ``devices``, ``jax.make_mesh`` picks a performance-aware
    device order over all devices; with them, the first
    ``prod(axis_shapes)`` of ``devices`` are used in order.  Raises
    ``ValueError`` when fewer devices exist than the shape needs.
    """
    shape = tuple(int(s) for s in axis_shapes)
    names = tuple(axis_names)
    auto = (jax.sharding.AxisType.Auto,) * len(names)
    if devices is not None:
        need = int(np.prod(shape)) if shape else 1
        devices = list(devices)
        if need > len(devices):
            raise ValueError(f"mesh shape {shape} needs {need} devices, "
                             f"have {len(devices)}")
        devices = devices[:need]
    return jax.make_mesh(shape, names, axis_types=auto, devices=devices)


def mesh_in_context() -> bool:
    """Whether a device mesh is in context: ``jax.set_mesh(mesh)`` sets
    the abstract mesh, the ``with mesh:`` form only the physical one."""
    from jax._src.mesh import thread_resources
    return not (jax.sharding.get_abstract_mesh().empty
                and thread_resources.env.physical_mesh.empty)


def device_mesh_1d(n_devices: int | None = None, axis_name: str = "scenarios"):
    """A 1-D mesh over the first ``n_devices`` devices (default: all) —
    the scenario-axis sharding the distributed sweep executor maps over.
    Emulate several devices on CPU with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before the
    first jax import)."""
    n = jax.device_count() if n_devices is None else int(n_devices)
    return make_mesh((n,), (axis_name,), devices=jax.devices()[:n])


def padded_size(n: int, n_shards: int) -> int:
    """Smallest multiple of ``n_shards`` that holds ``n`` rows (minimum
    one row per shard, so a shard is never zero-sized)."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    return max(1, -(-n // n_shards)) * n_shards


def pad_to_multiple(a, n_pad: int, axis: int = 0):
    """Edge-pad ``a`` along ``axis`` up to ``n_pad`` rows (no-op when
    already long enough).  Edge mode keeps padding rows finite and
    physically plausible, so masked lanes can never poison reductions
    with NaN/inf."""
    a = np.asarray(a)
    k = n_pad - a.shape[axis]
    if k <= 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, k)
    return np.pad(a, pad, mode="edge")


def normalize_cost_analysis(compiled) -> dict:
    """Return ``compiled.cost_analysis()`` as a plain dict.

    Some backends return ``None`` or raise.  Callers always get a dict
    (possibly empty) — never an exception — but a *raising* backend is
    reported via a warning so a run recorded with zeroed flops/bytes is
    traceable to its cause.
    """
    try:
        cost = compiled.cost_analysis()
    except Exception as e:
        import warnings
        warnings.warn(f"cost_analysis() failed ({e!r}); "
                      "proceeding with empty cost data", RuntimeWarning)
        return {}
    return dict(cost) if cost else {}
