"""Training driver: sharded train loop + fault-tolerant checkpointing.

Usable at every scale: reduced configs on this container's CPU devices, or
the production mesh on a real pod (same code path — only the mesh differs).

Fault-tolerance contract (DESIGN.md §4):
  * restart-safe: on launch, restores the latest checkpoint if present;
  * elastic: checkpoints are mesh-independent, so a restore may use a
    different device count / mesh shape;
  * deterministic data: batches are pure functions of (seed, step), so a
    restore resumes the exact batch stream — and straggler re-issue is a
    recompute, not a replay buffer.
"""
from __future__ import annotations

import argparse
import pathlib
import time

import jax
import numpy as np

from ..configs import get_arch
from ..models import factory
from .compile_cache import use_compile_cache
from .mesh import make_mesh
from ..models.config import ShapeConfig
from ..parallel import batch_pspecs, named, param_pspecs, zero1_pspecs
from ..train import checkpoint as ckpt
from ..train.data import make_data
from ..train.loop import make_train_step
from ..train.optimizer import AdamWConfig, adamw_init


def train(cfg, shape: ShapeConfig, mesh, n_steps: int,
          opt_cfg: AdamWConfig | None = None, n_micro: int = 1,
          ckpt_dir=None, ckpt_every: int = 50, restore: bool = True,
          zero1: bool = True, log_every: int = 10, seed: int = 0,
          fail_at_step: int | None = None):
    """Returns (params, history list of dicts)."""
    from jax.sharding import PartitionSpec as P
    from ..parallel import data_axes
    opt_cfg = opt_cfg or AdamWConfig(total_steps=n_steps)
    model = factory.make_model(
        cfg, act_pspec=P(data_axes(mesh), None, None))
    data = make_data(cfg, shape, seed=seed)

    pspecs = param_pspecs(factory.abstract_params(cfg))
    pshard = named(mesh, pspecs)
    abstract = factory.abstract_params(cfg)
    o_pspecs = {"mu": zero1_pspecs(abstract, pspecs, mesh) if zero1 else pspecs,
                "nu": zero1_pspecs(abstract, pspecs, mesh) if zero1 else pspecs,
                "count": jax.sharding.PartitionSpec()}
    oshard = named(mesh, o_pspecs)

    with mesh:
        init_fn = jax.jit(model.init, out_shardings=pshard)
        params = init_fn(jax.random.PRNGKey(seed))
        opt_state = jax.jit(adamw_init, out_shardings=oshard)(params)

        start_step = 0
        saver = None
        if ckpt_dir is not None:
            saver = ckpt.AsyncCheckpointer(ckpt_dir)
            latest = ckpt.latest_step(ckpt_dir)
            if restore and latest is not None:
                tree = {"params": params, "opt": opt_state}
                shards = {"params": pshard, "opt": oshard}
                restored, extra = ckpt.restore(ckpt_dir, latest, tree, shards)
                params, opt_state = restored["params"], restored["opt"]
                start_step = int(extra.get("step", latest)) + 1
                print(f"[train] restored step {latest}, resuming at "
                      f"{start_step}")

        batch0 = data.batch(0)
        bshard = named(mesh, batch_pspecs(batch0, mesh))
        step_fn = jax.jit(
            make_train_step(model.loss, opt_cfg, n_micro=n_micro),
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1))
        # the step is traced, not static: one compile for every step
        batch_fn = jax.jit(data.batch, out_shardings=bshard)

        history = []
        t0 = time.time()
        for step in range(start_step, n_steps):
            if fail_at_step is not None and step == fail_at_step:
                raise RuntimeError(f"injected failure at step {step}")
            batch = batch_fn(step)
            params, opt_state, m = step_fn(params, opt_state, batch)
            if step % log_every == 0 or step == n_steps - 1:
                loss = float(m.loss)
                history.append({"step": step, "loss": loss,
                                "grad_norm": float(m.grad_norm),
                                "lr": float(m.lr),
                                "elapsed_s": time.time() - t0})
                print(f"[train] step {step:5d} loss {loss:8.4f} "
                      f"gnorm {float(m.grad_norm):7.3f}")
            if saver is not None and step % ckpt_every == 0 and step > 0:
                saver.save(step, {"params": params, "opt": opt_state},
                           {"step": step})
        if saver is not None:
            saver.save(n_steps - 1, {"params": params, "opt": opt_state},
                       {"step": n_steps - 1})
            saver.wait()
    return params, history


# --------------------------------------------------------------------------
# IR-checked entry point (repro.analysis.ircheck registration)
# --------------------------------------------------------------------------

def _ircheck_train_step_spec():
    """The jitted train step exactly as :func:`train` builds it — same
    ``make_train_step`` product, same ``donate_argnums=(0, 1)`` — traced
    over a reduced config with abstract params/opt-state/batch (sharding
    annotations omitted: on one device they are identity, and the IR
    passes target donation/liveness/precision, not placement)."""
    from ..analysis.ircheck import EntrySpec
    from ..configs import get_arch
    from ..train.optimizer import adamw_init

    cfg = get_arch("qwen2.5-3b").reduced()
    model = factory.make_model(cfg, moe_impl="dense")
    shape = ShapeConfig("ircheck", "train", 16, 2)
    batch = factory.make_inputs(cfg, shape, abstract=True)
    params = factory.abstract_params(cfg)
    opt_state = jax.eval_shape(adamw_init, params)
    opt_cfg = AdamWConfig(total_steps=10)
    step_fn = jax.jit(make_train_step(model.loss, opt_cfg, n_micro=1),
                      donate_argnums=(0, 1))
    return EntrySpec(name="train.step", fn=step_fn,
                     args=(params, opt_state, batch),
                     donate_argnums=(0, 1))


def register_ircheck_entrypoints(register) -> None:
    """Register the train step's representative traced configuration
    with ``repro.analysis.ircheck``."""
    register("train.step", _ircheck_train_step_spec)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="training driver")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config of the same family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at-step", type=int, default=None)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("cli", "train", args.seq, args.batch)
    n = len(jax.devices())
    mesh = make_mesh((1, n) if n > 1 else (1, 1), ("data", "model"))
    _, history = train(cfg, shape, mesh, args.steps, n_micro=args.micro,
                       ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                       fail_at_step=args.fail_at_step)
    print(f"final loss: {history[-1]['loss']:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
