"""Random weights from ``--seed``, made on the device in one jitted call.

The tree has the layout of the model's own ``init`` (read with
``jax.eval_shape``, so nothing is drawn twice), in the configuration's
serving type.  Each leaf is drawn at the scale the model's initialiser
uses: ``1/sqrt(fan_in)`` for projections, further divided by
``sqrt(layers)`` for the two that write the residual stream (``wo`` and
``w_down``), 0.02 for the embedding table, and ``1/sqrt(d_model)`` for
an untied head.  Norm scales are ``1 + 0.1 N(0, 1)`` and biases
``0.1 N(0, 1)`` instead of the initialiser's ones and zeros, so that the
reference comparison sees every term of the layer.
"""
from __future__ import annotations

import math


def _scale(name: str, shape, n_layers: int) -> tuple:
    """``(offset, std)`` of the leaf called ``name``."""
    if name.endswith("norm"):
        return 1.0, 0.1
    if name.startswith("b"):
        return 0.0, 0.1
    if name == "table":
        return 0.0, 0.02
    std = 1.0 / math.sqrt(shape[-2])
    if name in ("wo", "w_down"):
        std /= math.sqrt(n_layers)
    return 0.0, std


def make_params(jax, model, key):
    """Draw the model's parameter tree from ``key`` in one jitted call."""
    import jax.numpy as jnp
    abstract = jax.eval_shape(model.init, key)
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    n_layers = model.cfg.n_layers

    def one(k, shape, dtype, off, std):
        z = jax.random.normal(k, shape, jnp.float32)
        return (off + std * z).astype(dtype)

    def draw(k):
        leaves = []
        for i, (path, leaf) in enumerate(paths):
            keys = [str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path]
            off, std = _scale(keys[-1], leaf.shape, n_layers)
            ki = jax.random.fold_in(k, i)
            if keys[0] == "stack":      # one layer at a time: small peak
                leaves.append(jax.lax.map(
                    lambda j, ki=ki, leaf=leaf, off=off, std=std: one(
                        jax.random.fold_in(ki, j), leaf.shape[1:],
                        leaf.dtype, off, std),
                    jnp.arange(leaf.shape[0])))
            else:
                leaves.append(one(ki, leaf.shape, leaf.dtype, off, std))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(draw)(key)
