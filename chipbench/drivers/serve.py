"""Driver for the serving engine: open-loop requests on the wall clock.

Set-up draws the weights from the seed on the device, builds the program's
``PagedContinuousEngine`` with the configuration's slots, length and block
size, and warms every shape the window uses (the chunk-prefill step, the
paged decode step, both samplers, and the slice of the prefill logits at
each offset within a block).  The window submits each request when it is
due and calls ``engine.step()`` whenever there is work; after the window
closes, the requests already due are drained.  Latency is timed from when
each request was due.  The engine is read only through its public surface:
``submit``, ``step``, ``run`` (which hands back every request's tokens
once all have retired) and the ``req_times`` stamps.  The check runs the float32 reference over a seeded
sample of finished requests, the longest among them, and reads how far
below the reference's best each served (greedy) token lies.
"""
from __future__ import annotations

import math
import time

import numpy as np

from chipbench import generate, lm
from chipbench.harness import Check, key_from_seed, percentile


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.cell.config
        self.m = self.cfg["model"]
        self.tr = ctx.cell.traffic
        self.attempted = self.failed = 0

    # ------------------------------------------------------------ set-up
    def setup(self):
        jax = self.ctx.jax
        from chipbench.weights import make_params
        from repro.serve.paged import PagedContinuousEngine
        self.model = lm.make_model(self.cfg)
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
        """One request per prompt length 1..block_size: every offset of the
        last prompt token in its chunk, two tokens each."""
        rng = generate.rng_for(self.ctx.seed, 7)
        self.engine.run([(rng.integers(0, self.m["vocab_size"], size=n), 2)
                         for n in range(1, bs + 1)])

    # ------------------------------------------------------------ window
    def window(self, win):
        jax = self.ctx.jax
        reqs = generate.open_loop(self.tr, win.seconds, self.ctx.seed,
                                  self.m["vocab_size"])
        self.reqs = reqs
        self.rid = [None] * len(reqs)          # request index -> engine rid
        self.due = [0.0] * len(reqs)           # absolute due times
        self.sent = [0.0] * len(reqs)
        self.admit = {}                        # request index -> step start
        self.times = {}                        # request index -> token times
        self.steps = []                        # (t0, t1, decoded, attended)
        self.pending = set()                   # submitted, not yet admitted
        self.flight = set()                    # admitted, not yet done
        self.wrong = set()                     # engine and harness disagree
        self.outputs = {}
        nxt = 0
        t0 = self.t0 = win.t0
        while True:
            now = time.perf_counter()
            while nxt < len(reqs) and t0 + reqs[nxt].due <= now:
                self._submit(nxt, t0)
                nxt += 1
            if not win.running():
                break
            if self.pending or self.flight:
                self._step()
            else:
                nxt_due = reqs[nxt].due if nxt < len(reqs) else win.seconds
                with jax.profiler.TraceAnnotation("cb.wait"):
                    time.sleep(max(0.0, t0 + min(nxt_due, win.seconds)
                                   - now))
        while nxt < len(reqs):                 # due in the window, late
            self._submit(nxt, t0)
            nxt += 1
        self.n_due = len(reqs)

    def _submit(self, i, t0):
        r = self.reqs[i]
        self.due[i] = t0 + r.due
        self.sent[i] = time.perf_counter()
        self.rid[i] = self.engine.submit(r.prompt, r.max_new)
        self.pending.add(i)
        self.attempted += 1

    def _step(self):
        """One ``engine.step()``, read through the engine's public surface:
        ``req_times[rid]`` gains ``"first"`` when the request is admitted
        (its first token) and ``"done"`` when it retires.  A step admits
        what fits and then decodes every admitted request once, so each
        request in flight after the step's admissions gets one token at
        the step's end until it has ``max_new``."""
        jax = self.ctx.jax
        e = self.engine
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("cb.step"):
            e.step()
        t1 = time.perf_counter()
        for i in [i for i in self.pending
                  if "first" in e.req_times.get(self.rid[i], ())]:
            self.pending.discard(i)
            self.flight.add(i)
            self.admit[i] = t0
            self.times[i] = [e.req_times[self.rid[i]]["first"]]
        decoded = attended = 0
        for i in list(self.flight):
            ts = self.times[i]
            want = self.reqs[i].max_new
            if len(ts) < want:
                decoded += 1
                attended += len(self.reqs[i].prompt) + len(ts)
                ts.append(t1)
            done = "done" in e.req_times[self.rid[i]]
            if done or len(ts) == want:
                self.flight.discard(i)
                if not (done and len(ts) == want):
                    self.wrong.add(i)
        self.steps.append((t0, t1, decoded, attended))

    def drain(self):
        """Step until every request due in the window has retired, or
        ``drain_seconds`` after the close; then collect the outputs."""
        stop = time.perf_counter() + float(self.tr["drain_seconds"])
        while (self.pending or self.flight) and time.perf_counter() < stop:
            self._step()
        if self.pending or self.flight:
            self.ctx.say(f"drain: {len(self.pending) + len(self.flight)} "
                         "requests unfinished")
            return
        outs = self.engine.run()           # every submitted request's tokens
        by_rid = {rid: i for i, rid in enumerate(self.rid)}
        for rid, out in zip(sorted(by_rid), outs):
            i = by_rid[rid]
            self.outputs[i] = np.asarray(out)
            if len(out) != len(self.times.get(i, ())):
                self.wrong.add(i)

    # ------------------------------------------------------------ results
    def end_to_end(self, window_s: float) -> dict:
        ttft, gaps = [], []
        for i in range(self.n_due):
            if not self._finished(i):
                self.failed += 1
                ttft.append(math.inf)
                continue
            ts = self.times[i]
            ttft.append(ts[0] - self.due[i])
            gaps += [b - a for a, b in zip(ts, ts[1:])]
        late = [s - d for s, d in zip(self.sent, self.due)]
        waits = [self.admit[i] - self.due[i] for i in self.admit
                 if self.admit[i] < self.t0 + window_s]
        c = self.ctx.counters
        c["admit_wait_s"] = waits
        c["steps"] = self.steps
        c["prompt_tokens"] = sum(len(r.prompt) for r in self.reqs)
        c["output_tokens"] = sum(r.max_new for r in self.reqs)
        c["ctx_sum"] = sum(_ctx_sum(len(r.prompt), r.max_new)
                           for r in self.reqs)
        c["t0"] = self.t0
        c["drain_end"] = self.steps[-1][1] if self.steps else self.t0
        self.ctx.say(
            f"requests {self.n_due} due, {self.failed} failed; generator "
            f"late p95 {percentile(late, 95) * 1e3:.3f} ms max "
            f"{max(late) * 1e3:.3f} ms; admit wait p95 "
            f"{percentile(waits, 95) * 1e3:.1f} ms; steps {len(self.steps)}")
        return {"ttft_p95_ms": percentile(ttft, 95) * 1e3,
                "itl_p95_ms": percentile(gaps, 95) * 1e3}

    def _finished(self, i) -> bool:
        """Retired with all its tokens, by the engine and by the harness."""
        return (i in self.outputs and i not in self.wrong
                and len(self.outputs[i]) == self.reqs[i].max_new)

    def release(self):
        self.engine = self.params = None

    # ------------------------------------------------------------ check
    def sample(self) -> list:
        """A seeded sample of the finished requests, the longest first."""
        done = [i for i in range(self.n_due) if self._finished(i)]
        if not done:
            return []
        size = lambda i: len(self.reqs[i].prompt) + self.reqs[i].max_new
        longest = max(done, key=size)
        rest = sorted(set(done) - {longest})
        k = min(int(self.tr["check_requests"]) - 1, len(rest))
        pick = generate.rng_for(self.ctx.seed, 8).choice(
            len(rest), size=k, replace=False) if k else []
        return [longest] + [rest[j] for j in sorted(pick)]

    def reference_gaps(self, picks, low=False) -> dict:
        """Widest gap, in standard deviations of the reference logit row,
        by which a served token (``low=False``), or the token the float8
        control puts first (``low=True``), lies below the reference's best.
        """
        jax = self.ctx.jax
        import jax.numpy as jnp
        from chipbench.reference import dense_lm as ref
        from chipbench.weights import make_params
        params = make_params(jax, self.model,
                             key_from_seed(jax, self.ctx.seed))
        width = self.cfg["engine"]["max_len"]
        k_max = self.tr["output"].get("hi", self.tr["output"].get("value"))

        @jax.jit
        def fn(p, toks, at):
            want = ref.logits_at(self.m, p, toks, at)
            mu = want.mean(-1, keepdims=True)
            sd = want.std(-1, keepdims=True)
            got = ref.logits_at(self.m, p, toks, at, low=True) if low \
                else None
            return want, mu, sd, got

        worst, n_tok = 0.0, 0
        for i in picks:
            r = self.reqs[i]
            out = self.outputs[i]
            seq = np.concatenate([r.prompt, out[:-1]]).astype(np.int32)
            toks = np.zeros((1, width), np.int32)
            toks[0, :len(seq)] = seq
            at = np.full((1, k_max), len(r.prompt) - 1, np.int32)
            at[0, :len(out)] = len(r.prompt) - 1 + np.arange(len(out))
            want, _, sd, got = fn(params, jnp.asarray(toks), jnp.asarray(at))
            want = np.asarray(want[0, :len(out)], np.float64)
            sd = np.asarray(sd[0, :len(out), 0], np.float64)
            tok = np.asarray(got[0, :len(out)]).argmax(-1) if low else out
            gap = (want.max(-1) - want[np.arange(len(out)), tok]) / sd
            worst = max(worst, float(gap.max()))
            n_tok += len(out)
        return {"gap_std": worst, "tokens": n_tok}

    def check(self) -> list:
        picks = self.sample()
        if not picks:
            return [Check("requests_checked", 0.0, -1.0)]
        res = self.reference_gaps(picks)
        self.ctx.say(f"reference: {len(picks)} requests, {res['tokens']} "
                     "served tokens compared")
        return [Check("served_gap_std", res["gap_std"],
                      self.tr["limits"]["served_gap_std"])]


def _ctx_sum(prompt: int, out: int) -> float:
    """Attended positions summed over a request's prompt and its decoded
    tokens (token at position p attends to p + 1 positions)."""
    n = prompt + out
    return n * (n + 1) / 2.0
