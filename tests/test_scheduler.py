"""Continuous-batching scheduler tests on the one continuous engine:
greedy parity with the static engine (pinned acceptance test), staggered
arrivals with slot reuse, eos/length retirement and chunk-boundary
prompts for attention, SSM and hybrid archs, telemetry, and the advisor
bridge."""
from dataclasses import dataclass

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models.factory import make_model
from repro.serve import PagedContinuousEngine, ServeEngine, ServeStats

KEY = jax.random.PRNGKey(0)
MAX_LEN = 24
BS = 4                                        # block size = prefill chunk
#: an attention stack, a pure SSM stack and a hybrid (reduced)
SERVE_ARCHS = ["qwen2.5-3b", "falcon-mamba-7b", "jamba-v0.1-52b"]


@dataclass
class Served:
    """One arch's model, static reference and continuous engine."""

    cfg: object
    model: object
    params: dict
    static: ServeEngine
    engine: PagedContinuousEngine

    def prompts(self, key, b, s):
        return np.asarray(jax.random.randint(
            jax.random.PRNGKey(key), (b, s), 0, self.cfg.vocab_size),
            dtype=np.int32)

    def fresh_stats(self) -> PagedContinuousEngine:
        """The shared engine with its telemetry zeroed (its compiled
        steps, pools and slots carry over, as between requests)."""
        self.engine.stats = ServeStats(n_slots=self.engine.n_slots)
        return self.engine


@pytest.fixture(scope="module")
def served():
    """One engine and one static reference per arch for the whole module,
    so every case of an arch reuses the same compiled steps."""
    cache = {}

    def get(arch: str) -> Served:
        if arch not in cache:
            cfg = ARCHS[arch].reduced()
            model = make_model(cfg, moe_impl="dense")
            params = model.init(KEY)
            cache[arch] = Served(
                cfg, model, params,
                ServeEngine(model=model, params=params, max_len=MAX_LEN),
                PagedContinuousEngine(model=model, params=params, n_slots=2,
                                      max_len=MAX_LEN, block_size=BS))
        return cache[arch]
    return get


def test_continuous_matches_static_greedy(served):
    """PINNED: all requests at t=0, fitting one batch -> token-for-token
    identical to the static engine's greedy outputs."""
    s = served("qwen2.5-3b")
    prompts = s.prompts(1, 2, 8)
    ref = np.asarray(s.static.generate(prompts, 6))
    eng = s.fresh_stats()
    outs = eng.run([(prompts[i], 6) for i in range(2)])
    np.testing.assert_array_equal(np.stack(outs), ref)
    assert eng.stats.occupancy == 1.0         # both slots busy every step
    assert eng.stats.decode_steps == 5        # 6 tokens = prefill + 5 decodes


@pytest.mark.parametrize("arch", SERVE_ARCHS)
def test_staggered_arrivals_and_slot_reuse(served, arch):
    """More requests than slots with staggered arrivals: every request's
    greedy continuation matches its static single-request reference, so
    admission into a previously-used slot (and its reused blocks or
    recurrent row) carries no state over."""
    s = served(arch)
    prompts = s.prompts(3, 4, 7)
    ref = np.asarray(s.static.generate(prompts, 6))
    eng = s.fresh_stats()
    outs = eng.run([(prompts[i], 6, 3 * i) for i in range(4)])
    assert len(outs) == 4
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(o, ref[i])
    st = eng.stats
    assert st.completed == 4 and st.prefills == 4
    assert 0.0 < st.occupancy <= 1.0          # ramp-up/down leaves gaps
    assert st.slot_steps == 4 * 5             # 5 decode tokens per request
    assert eng._pool.in_use == 0              # everything released


@pytest.mark.parametrize("arch", SERVE_ARCHS)
def test_eos_retirement_frees_slot(served, arch):
    """A request retires the moment it samples eos; the freed slot admits
    the next queued request, whose output is unaffected."""
    s = served(arch)
    prompts = s.prompts(4, 3, 8)
    ref = np.asarray(s.static.generate(prompts, 6))
    eos = int(ref[0, 2])                      # row 0 will stop here
    eng = s.fresh_stats()
    eng.eos_id = eos
    try:
        outs = eng.run([(prompts[i], 6) for i in range(3)])
    finally:
        eng.eos_id = None
    # row 0 ends at its first eos occurrence (eos kept, nothing after)
    first = list(ref[0]).index(eos) + 1
    np.testing.assert_array_equal(outs[0], ref[0][:first])
    for i in (1, 2):                          # truncated at first eos if any
        exp = list(ref[i])
        exp = exp[:exp.index(eos) + 1] if eos in exp else exp
        np.testing.assert_array_equal(outs[i], np.asarray(exp))
    assert eng._pool.in_use == 0


@pytest.mark.parametrize("n", [1, BS - 1, BS, BS + 1, 2 * BS + 1])
@pytest.mark.parametrize("arch", SERVE_ARCHS)
def test_chunk_boundary_prompts_match_static(served, arch, n):
    """Prompts one token, one short of a chunk, a chunk, one past it and
    two chunks and one stream through ``ceil(n / block_size)`` chunk
    steps and decode greedily exactly as the static engine: the padded
    tail of the last chunk is inert for attention and never folds into
    the recurrent state."""
    s = served(arch)
    prompt = s.prompts(10 + n, 1, n)
    ref = np.asarray(s.static.generate(prompt, 5))[0]
    eng = s.fresh_stats()
    (out,) = eng.run([(prompt[0], 5)])
    np.testing.assert_array_equal(out, ref)
    assert eng.stats.prefills_by_bucket == {f"prefill_chunk@{BS}":
                                            -(-n // BS)}


def test_varied_lengths_and_budget_cap(served):
    """Per-request max_new_tokens are honored; a request whose budget
    exceeds the cache room is capped at max_len - prompt_len."""
    s = served("qwen2.5-3b")
    prompts = s.prompts(5, 2, 8)
    outs = s.fresh_stats().run([(prompts[0], 3), (prompts[1], 99)])
    assert len(outs[0]) == 3
    assert len(outs[1]) == MAX_LEN - 8        # capped by cache room


def test_submit_validation(served):
    s = served("qwen2.5-3b")
    eng = PagedContinuousEngine(model=s.model, params=s.params, n_slots=2,
                                max_len=12, block_size=BS)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(0, np.int32), 4)       # empty prompt
    with pytest.raises(ValueError):
        eng.submit(np.zeros(12, np.int32), 4)      # no room to generate
    rid = eng.submit(np.zeros(4, np.int32), 0)     # nothing to generate
    assert rid == 0
    outs = eng.run()
    assert len(outs) == 1 and outs[0].shape == (0,)


def test_compiled_steps_for_advisor(served):
    """compiled_steps exposes the decode and the one chunk-prefill step,
    which ``price(engine, grid)`` prices in one batched call."""
    from repro.core import CommAdvisor, MultiSweepResult, price

    s = served("qwen2.5-3b")
    eng = PagedContinuousEngine(model=s.model, params=s.params, n_slots=2,
                                max_len=16, block_size=8)
    steps = eng.compiled_steps()
    assert set(steps) == {"decode", "prefill_chunk@8"}
    assert all(hasattr(c, "as_text") for c in steps.values())

    res = price(eng, CommAdvisor().default_grid(2, 2))
    assert isinstance(res, MultiSweepResult)
    assert set(res.names) == {"decode", "prefill_chunk@8"}
    assert len(res) == 2
    # single-device steps have no collectives: a no-op deployment
    assert res.predicted_speedup().shape == (4,)
    np.testing.assert_allclose(res.predicted_speedup(), 1.0)


def test_static_engine_compiled_steps(served):
    """The static engine exposes the same advisor bridge (one prefill
    shape + the decode step)."""
    steps = served("qwen2.5-3b").static.compiled_steps(batch_size=2,
                                                        prompt_len=8)
    assert set(steps) == {"prefill@8", "decode"}
    assert all(hasattr(c, "as_text") for c in steps.values())
