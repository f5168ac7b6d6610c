"""Driver for the serving engine on a Jamba hybrid (Mamba-1 + attention).

The window, the drain, the latencies and the check are those of
``drivers/serve.py``; this driver changes the model construction, the
warm-up and the reference, and reads the model's published keys from
the configuration file's top level.  The model comes from
``chipbench/hybrid_lm.py`` and its weights from the seed as in
``weights.py``, with the Mamba leaves drawn as Mamba initialises them
(S4D-real ``A_log``, ``dt`` bias with ``softplus`` in [1e-3, 1e-1]) so
that the recurrence keeps a long memory, and ``D``, the conv bias and
the inner norms perturbed.  Since the chunk
step takes the index of a prompt's last token as an argument, one request
of a block and one token compiles every shape the window uses: the chunk
step, the paged decode step and both samplers.  The check runs
``reference/jamba_lm.py``.  For ``prefill_roofline.jamba`` the driver also
records each admission's step and prompt length (``admissions``).
"""
from __future__ import annotations

import math

import numpy as np

from chipbench import generate, hybrid_lm
from chipbench.drivers import serve
from chipbench.harness import key_from_seed
from chipbench.weights import _scale


def _draw(jax, name, k, shape, dtype, off, std):
    """One layer's leaf ``name``: Mamba's initialiser where the name is
    one of its special leaves, else ``off + std N(0, 1)``."""
    import jax.numpy as jnp
    z = jax.random.normal(k, shape, jnp.float32)
    if name == "A_log":                          # S4D-real, perturbed
        n = jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)
        return (jnp.log(n) + 0.1 * z).astype(dtype)
    if name == "dt_bias":                        # softplus^-1 of dt
        u = jax.random.uniform(k, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return (off + std * z).astype(dtype)


def scale(name: str, shape, n_layers: int) -> tuple:
    """``(offset, std)`` of leaf ``name``, as ``weights._scale`` for the
    leaves it knows; ``D`` at 1 + 0.1 N, the conv bias at 0.1 N, and
    ``out_proj`` (which writes the residual stream) over sqrt(layers)."""
    if name == "D":
        return 1.0, 0.1
    if name == "conv_b":
        return 0.0, 0.1
    off, std = _scale(name, shape, n_layers)
    if name == "out_proj":
        std /= math.sqrt(n_layers)
    return off, std


def make_params(jax, model, key):
    """Draw the model's parameter tree from ``key`` in one jitted call,
    one layer at a time for the stacked leaves (a small peak)."""
    import jax.numpy as jnp
    abstract = jax.eval_shape(model.init, key)
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    n_layers = model.cfg.n_layers

    def draw(k):
        leaves = []
        for i, (path, leaf) in enumerate(paths):
            keys = [str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path]
            name = keys[-1]
            off, std = scale(name, leaf.shape, n_layers)
            ki = jax.random.fold_in(k, i)
            if keys[0] == "stack":
                leaves.append(jax.lax.map(
                    lambda j, ki=ki, leaf=leaf, off=off, std=std, name=name:
                    _draw(jax, name, jax.random.fold_in(ki, j),
                          leaf.shape[1:], leaf.dtype, off, std),
                    jnp.arange(leaf.shape[0])))
            else:
                leaves.append(_draw(jax, name, ki, leaf.shape, leaf.dtype,
                                    off, std))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(draw)(key)


class Driver(serve.Driver):
    def __init__(self, ctx):
        # the published keys sit at the configuration file's top level
        self.ctx = ctx
        self.cfg = self.m = ctx.cell.config
        self.tr = ctx.cell.traffic
        self.attempted = self.failed = 0

    # ------------------------------------------------------------ set-up
    def setup(self):
        jax = self.ctx.jax
        from repro.serve.paged import PagedContinuousEngine
        self.model = hybrid_lm.make_model(self.cfg)
        self.params = make_params(jax, self.model,
                                  key_from_seed(jax, self.ctx.seed))
        jax.block_until_ready(self.params)
        self.ctx.mark("weights")
        eng = self.cfg["engine"]
        self.engine = PagedContinuousEngine(
            model=self.model, params=self.params, n_slots=eng["n_slots"],
            max_len=eng["max_len"], block_size=eng["block_size"],
            temperature=0.0)
        self.ctx.mark("engine")
        self._warm(eng["block_size"])
        self.ctx.mark("warm-up")

    def _warm(self, bs: int):
        """One request of ``bs + 1`` tokens (a full chunk and a padded
        one), two tokens: the chunk step, the decode and both samplers."""
        rng = generate.rng_for(self.ctx.seed, 7)
        self.engine.run([(rng.integers(0, self.m["vocab_size"],
                                       size=bs + 1), 2)])

    # ------------------------------------------------------------ window
    def window(self, win):
        self.admissions = []       # (step start, step end, prompt length)
        super().window(win)

    def _step(self):
        before = set(self.admit)
        super()._step()
        t0, t1 = self.steps[-1][:2]
        self.admissions += [(t0, t1, len(self.reqs[i].prompt))
                            for i in sorted(set(self.admit) - before)]

    def end_to_end(self, window_s: float) -> dict:
        out = super().end_to_end(window_s)
        self.ctx.counters["admissions"] = self.admissions
        return out

    # ------------------------------------------------------------ check
    def reference_gaps(self, picks, low=False) -> dict:
        """As ``serve.Driver.reference_gaps``, on ``reference/jamba_lm``."""
        jax = self.ctx.jax
        import jax.numpy as jnp
        from chipbench.reference.jamba_lm import Reference
        params = make_params(jax, self.model,
                             key_from_seed(jax, self.ctx.seed))
        width = self.cfg["engine"]["max_len"]
        k_max = self.tr["output"].get("hi", self.tr["output"].get("value"))
        ref = Reference(self.m)
        ctl = Reference(self.m, low=True) if low else None
        worst, n_tok = 0.0, 0
        for i in picks:
            r = self.reqs[i]
            out = self.outputs[i]
            seq = np.concatenate([r.prompt, out[:-1]]).astype(np.int32)
            toks = np.zeros((1, width), np.int32)
            toks[0, :len(seq)] = seq
            at = np.full((1, k_max), len(r.prompt) - 1, np.int32)
            at[0, :len(out)] = len(r.prompt) - 1 + np.arange(len(out))
            toks, at = jnp.asarray(toks), jnp.asarray(at)
            want = np.asarray(ref.logits_at(params, toks, at)[0, :len(out)],
                              np.float64)
            sd = want.std(-1)
            tok = np.asarray(ctl.logits_at(params, toks, at)[0, :len(out)]
                             ).argmax(-1) if low else out
            gap = (want.max(-1) - want[np.arange(len(out)), tok]) / sd
            worst = max(worst, float(gap.max()))
            n_tok += len(out)
        return {"gap_std": worst, "tokens": n_tok}
