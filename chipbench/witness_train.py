#!/usr/bin/env python3
"""Witness for the training cell left out: bfloat16 parameters drop small
AdamW updates.

    python3 chipbench/witness_train.py --config qwen2.5-3b-d4 \\
        --seeds 1,2,3 [--batch 1] [--seq 512] [--no-f32] [--platform-ok]

One AdamW step of the program's train step (``train.loop.make_train_step``
with the default ``AdamWConfig``, as ``launch.train`` builds it on a
(1, 1) mesh) from the benchmark's seeded weights and a seeded batch, in the
configuration's bfloat16 and, as a second witness, with the same program
in float32.  Beside each, the float32 reference's first update: AdamW's
first step moves every element with a nonzero gradient by ``lr *
(sign(g) + wd * p)`` (``wd`` on matrices only).  Prints, per parameter
leaf, the share of elements each side moved and the norm of each side's
change over the reference's, as one JSON object per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def one_seed(jax, cfg: dict, seed: int, batch: int, seq: int,
             f32: bool = True) -> dict:
    import jax.numpy as jnp

    from chipbench import lm
    from chipbench.harness import key_from_seed
    from chipbench.reference import dense_lm as ref
    from chipbench.weights import make_params
    from repro.train.loop import make_train_step
    from repro.train.optimizer import AdamWConfig, adamw_init, \
        cosine_schedule

    m = cfg["model"]
    key = key_from_seed(jax, seed)
    tok = jax.random.randint(jax.random.fold_in(key, 99), (batch, seq + 1),
                             0, m["vocab_size"])
    data = {"tokens": tok[:, :-1], "targets": tok[:, 1:]}
    opt = AdamWConfig(total_steps=1000)
    lr = float(cosine_schedule(opt, 1))

    def program_step(dtype):
        c = dict(cfg, model=dict(m, torch_dtype=dtype))
        model = lm.make_model(c)
        p0 = make_params(jax, model, key)
        step = jax.jit(make_train_step(model.loss, opt), donate_argnums=(1,))
        p1, _, met = step(p0, adamw_init(p0), data)
        moved = jax.tree.map(lambda a, b: float(jnp.mean(
            (a != b).astype(jnp.float32))), p0, p1)
        change = jax.tree.map(lambda a, b: float(jnp.linalg.norm(
            (b.astype(jnp.float32) - a.astype(jnp.float32)).ravel())),
            p0, p1)
        loss = float(met.loss)
        del p1, met
        return p0, moved, change, loss

    nothing = (None, None, None, None)
    _, moved32, change32, loss32 = program_step("float32") if f32 \
        else nothing
    p0, moved16, change16, loss16 = program_step("bfloat16")

    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), p0)
    g = jax.grad(lambda p: ref.loss(m, p, data["tokens"],
                                    data["targets"]))(p32)

    def ref_update(p, gl):
        wd = opt.weight_decay if p.ndim >= 2 else 0.0
        return lr * (gl / (jnp.abs(gl) + opt.eps) + wd * p)

    upd = jax.jit(lambda p, g: jax.tree.map(ref_update, p, g))(p32, g)
    ref_moved = jax.tree.map(lambda u: jnp.mean((u != 0).astype(
        jnp.float32)), upd)
    ref_norm = jax.tree.map(lambda u: jnp.linalg.norm(u.ravel()), upd)

    leaves = {}
    flat = jax.tree_util.tree_flatten_with_path(ref_norm)[0]
    n = len(flat)
    for (path, rn), a, b, c, d, e in zip(
            flat, jax.tree.leaves(moved16), jax.tree.leaves(change16),
            jax.tree.leaves(moved32) if f32 else [None] * n,
            jax.tree.leaves(change32) if f32 else [None] * n,
            jax.tree.leaves(ref_moved)):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        rn = float(rn)
        leaves[name] = {
            "bf16_moved": float(a), "bf16_change_over_ref": float(b) / rn,
            "f32_moved": None if c is None else float(c),
            "f32_change_over_ref": None if d is None else float(d) / rn,
            "ref_moved": float(e)}
    return {"seed": seed, "lr_step1": lr, "loss_bf16": loss16,
            "loss_f32": loss32, "leaves": leaves}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="qwen2.5-3b-d4")
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--platform-ok", action="store_true",
                    help="run without a TPU too (a CPU witness at any size)")
    ap.add_argument("--no-f32", action="store_true",
                    help="leave out the float32 program (the second "
                    "witness), for sizes where it does not fit")
    ap.add_argument("--layers", type=int, default=0,
                    help="override the layer count (CPU witness only)")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu" and not args.platform_ok:
        print("no TPU (pass --platform-ok for a CPU witness)",
              file=sys.stderr)
        return 2
    cfg = json.loads((ROOT / "chipbench" / "configs" /
                      f"{args.config}.json").read_text())
    if args.layers:
        cfg["model"]["num_hidden_layers"] = args.layers
    for s in args.seeds.split(","):
        print(json.dumps(one_seed(jax, cfg, int(s), args.batch, args.seq,
                                  f32=not args.no_f32)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
