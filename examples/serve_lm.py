"""Batched serving: prefill a batch of prompts, decode continuations.

Static engine (one batch, ends together):
    PYTHONPATH=src python examples/serve_lm.py --new-tokens 24
Continuous batching (slots + queue, paged KV, staggered arrivals):
    PYTHONPATH=src python examples/serve_lm.py --continuous
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.models.factory import make_model
from repro.serve import PagedContinuousEngine, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--continuous", action="store_true")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--pool-blocks", type=int, default=0)
    args = ap.parse_args()

    cfg = get_arch(args.arch).reduced()
    model = make_model(cfg, moe_impl="dense")
    params = model.init(jax.random.PRNGKey(0))
    max_len = args.prompt_len + args.new_tokens
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size)

    if args.continuous:
        engine = PagedContinuousEngine(
            model=model, params=params, n_slots=max(2, args.batch // 2),
            max_len=max_len, temperature=args.temperature,
            block_size=args.block_size, pool_blocks=args.pool_blocks)
        # stagger arrivals and vary lengths — the scheduler keeps the decode
        # slots busy while requests come and go
        reqs = [(np.asarray(prompts)[i], args.new_tokens - 3 * (i % 3), 2 * i)
                for i in range(args.batch)]
        t0 = time.time()
        outs = engine.run(reqs)
        dt = time.time() - t0
        s = engine.stats
        n_tok = sum(len(o) for o in outs)
        print(f"{len(outs)} requests on {engine.n_slots} slots: {dt:.2f}s, "
              f"{n_tok} tokens ({n_tok / max(dt, 1e-9):.1f} tok/s incl. "
              f"compile), occupancy {s.occupancy:.2f}")
        for i, o in enumerate(outs[:3]):
            print(f"  request {i} ({len(o)} tokens): ...{o[:10].tolist()}")
        return

    engine = ServeEngine(model=model, params=params, max_len=max_len,
                         temperature=args.temperature)
    t0 = time.time()
    out = engine.generate(prompts, args.new_tokens)
    dt = time.time() - t0
    print(f"batch={args.batch} prompt={args.prompt_len} "
          f"new={args.new_tokens}: {dt:.2f}s "
          f"({args.batch*args.new_tokens/max(dt, 1e-9):.1f} tok/s incl. "
          f"compile)")
    for i in range(min(2, args.batch)):
        print(f"  request {i}: ...{out[i, :12].tolist()}")


if __name__ == "__main__":
    main()
