"""Paged-KV tests of the continuous engine: greedy parity with the static
engine (pinned acceptance test, chunked SSM / hybrid prefill), KV-bytes
scaling with actual sequence lengths, block free/reuse after eos
retirement, the decode's block reads, pool-exhaustion admission errors,
and backpressure.  Staggered arrivals, eos retirement and chunk-boundary
prompts on every arch are in ``test_scheduler.py``."""
import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models.factory import make_model
from repro.serve import PagedContinuousEngine, PoolExhausted, ServeEngine

CFG = ARCHS["qwen2.5-3b"].reduced()
KEY = jax.random.PRNGKey(0)
MAX_LEN = 24
BS = 4                                        # block size


@pytest.fixture(scope="module")
def model_params():
    model = make_model(CFG, moe_impl="dense")
    return model, model.init(KEY)


@pytest.fixture(scope="module")
def static(model_params):
    model, params = model_params
    return ServeEngine(model=model, params=params, max_len=MAX_LEN)


def _prompts(key, b, s):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(key), (b, s), 0,
                                         CFG.vocab_size), dtype=np.int32)


def _paged(model_params, **kw):
    model, params = model_params
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("block_size", BS)
    return PagedContinuousEngine(model=model, params=params, **kw)


def test_paged_matches_static_greedy(model_params, static):
    """PINNED: all requests at t=0 -> token-for-token identical to the
    static engine, with the prompt prefilled in block_size chunks."""
    model, params = model_params
    prompts = _prompts(1, 2, 8)
    ref = np.asarray(static.generate(prompts, 6))
    eng = _paged(model_params)
    outs = eng.run([(prompts[i], 6) for i in range(2)])
    np.testing.assert_array_equal(np.stack(outs), ref)
    assert eng.stats.prefills_by_bucket == {f"prefill_chunk@{BS}": 4}


def test_kv_bytes_scale_with_actual_lengths(model_params):
    """KV bytes scale with the sum of ACTUAL sequence lengths rounded up
    to the block size — not n_slots * max_len."""
    prompts = _prompts(3, 1, 9)
    eng = _paged(model_params)
    eng.run([(prompts[0], 6)])
    # final sequence writes positions 0..13 (prompt 9 + 5 decode writes)
    assert eng.kv_bytes_peak == -(-(9 + 6 - 1) // BS) * eng.block_bytes
    assert eng.kv_bytes_dense == 2 * (MAX_LEN // BS) * eng.block_bytes
    assert eng.kv_bytes_peak < eng.kv_bytes_dense
    assert eng.stats.kv_bytes_peak == eng.kv_bytes_peak
    assert eng.stats.kv_bytes_dense == eng.kv_bytes_dense
    assert eng.kv_bytes_in_use == 0           # released on retirement


def test_eos_retirement_frees_and_reuses_blocks(model_params, static):
    """A pool sized for exactly two concurrent requests still serves four:
    eos/length retirement returns blocks to the pool and later admissions
    reuse the same physical blocks (outputs stay correct)."""
    model, params = model_params
    prompts = _prompts(4, 4, 6)
    ref = np.asarray(static.generate(prompts, 5))
    eos = int(ref[0, 2])                      # row 0 retires early on eos
    need = -(-(6 + 5) // BS)                  # worst-case blocks per request
    eng = _paged(model_params, eos_id=eos, pool_blocks=2 * need)
    outs = eng.run([(prompts[i], 5) for i in range(4)])
    for i in range(4):
        exp = list(ref[i])
        exp = exp[:exp.index(eos) + 1] if eos in exp else exp
        assert list(outs[i]) == exp
    assert eng._pool.in_use == 0
    assert eng._pool.peak_in_use <= 2 * need  # reuse, not fresh blocks
    assert not eng._tables.any()              # all rows back to null block


def test_kv_block_reads_sum_the_live_blocks(model_params):
    """Staggered arrivals on two slots: ``ServeStats`` sums, step by step,
    the blocks each active slot's attention reads, ``ceil((pos + 1) /
    block_size)``, against ``n_slots * max_blocks`` for a full-width
    read."""
    eng = _paged(model_params)
    reads = []
    decode = eng._decode_active

    def watched():
        reads.append(sum(int(eng._pos[s]) // BS + 1
                         for s in range(eng.n_slots)
                         if eng._slot_req[s] is not None))
        return decode()

    eng._decode_active = watched
    prompts = _prompts(9, 4, 11)
    eng.run([(prompts[0][:3], 7, 0), (prompts[1], 4, 1),
             (prompts[2][:6], 9, 2), (prompts[3][:1], 5, 6)])
    assert len(reads) == eng.stats.decode_steps > 0
    assert eng.stats.kv_blocks_read == sum(reads)
    assert eng.stats.kv_blocks_full == \
        eng.stats.decode_steps * 2 * (MAX_LEN // BS)
    assert 0 < eng.stats.kv_blocks_read < eng.stats.kv_blocks_full


def _large_values(fn, args, limit):
    """Shapes of every array of ``limit`` elements or more that the traced
    program computes, sub-programs included; a Pallas kernel counts by its
    outputs (its body runs in the chip's on-core memory)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            found.extend(tuple(v.aval.shape) for v in eqn.outvars
                         if np.prod(v.aval.shape) >= limit)
            if eqn.primitive.name == "pallas_call":
                continue
            for p in eqn.params.values():
                for sub in p if isinstance(p, (list, tuple)) else (p,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_paged_decode_builds_no_full_width_cache():
    """The reduced engine the IR checker lowers: apart from the pools
    (updated in place), the decode step computes no array as large as
    every slot's cache at full width, ``n_slots * max_len * Hkv * D``."""
    from repro.serve.paged import _ircheck_engine
    eng = _ircheck_engine()
    cfg = eng.model.cfg
    pools = eng._step_structs()[0]
    pool_shapes = {tuple(x.shape) for x in jax.tree.leaves(pools)}
    limit = eng.n_slots * eng.max_len * cfg.n_kv_heads \
        * cfg.resolved_head_dim
    big = _large_values(eng._decode_paged,
                        (eng.params, *eng._step_structs()), limit)
    assert big and set(big) <= pool_shapes, set(big) - pool_shapes


def test_pool_exhaustion_raises_at_submit(model_params):
    """A request that could NEVER fit fails fast at submit() with a clear
    error, before anything is queued."""
    eng = _paged(model_params, pool_blocks=2)
    with pytest.raises(PoolExhausted, match="needs 4 KV blocks.*holds 2"):
        eng.submit(_prompts(5, 1, 9)[0], 6)
    assert not eng._queue and eng._pool.in_use == 0


def test_admission_backpressure(model_params, static):
    """A pool with room for only ONE in-flight request serves three in
    FIFO order: admission waits for blocks instead of failing."""
    prompts = _prompts(6, 3, 9)
    ref = np.asarray(static.generate(prompts, 6))
    eng = _paged(model_params, pool_blocks=4)  # = one request's worst case
    outs = eng.run([(prompts[i], 6) for i in range(3)])
    np.testing.assert_array_equal(np.stack(outs), ref)
    assert eng._pool.peak_in_use <= 4


def test_step_weights_reflect_observed_mix(model_params):
    """step_weights() reports the observed decode / chunk-prefill step mix
    (the dict MultiSweepResult.predicted_speedup(weights=) consumes)."""
    eng = _paged(model_params)
    eng.run([(_prompts(7, 1, 6)[0], 4)])
    w = eng.step_weights()
    assert w["decode"] == float(eng.stats.decode_steps) > 0
    assert w[f"prefill_chunk@{BS}"] == 2.0    # ceil(6 / 4) chunks


@pytest.mark.parametrize("arch", ["falcon-mamba-7b", "jamba-v0.1-52b"])
def test_ssm_archs_paged_parity(arch):
    """SSM / hybrid archs: recurrent state stays dense (O(1) per slot —
    nothing to page) and admission streams through the same chunk step
    as attention archs, the recurrent state resuming from the slot's row
    at every chunk (7-token prompts: a full chunk, then a padded one);
    attention KV (hybrid) is block-paged.  Three requests on two slots,
    so a reused slot starts its prompt from zero state.  Greedy outputs
    match the static engine."""
    cfg = ARCHS[arch].reduced()
    model = make_model(cfg, moe_impl="dense")
    params = model.init(KEY)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(8), (3, 7), 0, cfg.vocab_size), dtype=np.int32)
    ref = np.asarray(ServeEngine(model=model, params=params,
                                 max_len=16).generate(prompts, 5))
    eng = PagedContinuousEngine(model=model, params=params, n_slots=2,
                                max_len=16, block_size=4)
    outs = eng.run([(prompts[i], 5, i) for i in range(3)])
    for i in range(3):
        np.testing.assert_array_equal(outs[i], ref[i])
    assert eng.stats.prefills_by_bucket == {"prefill_chunk@4": 6}
    if arch == "falcon-mamba-7b":
        assert eng.block_bytes == 0           # no attention KV at all
    else:
        assert eng.kv_bytes_peak > 0          # hybrid pages its attn KV
    assert eng._pool.in_use == 0


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "falcon-mamba-7b",
                                  "jamba-v0.1-52b"])
def test_compiled_steps_every_arch(arch):
    """Every arch deploys the same two steps: the paged decode and the one
    chunk-prefill step (no per-length prefill for SSM archs)."""
    cfg = ARCHS[arch].reduced()
    model = make_model(cfg, moe_impl="dense")
    eng = PagedContinuousEngine(model=model, params=model.init(KEY),
                                n_slots=2, max_len=16, block_size=4)
    assert set(eng.compiled_steps()) == {"decode", "prefill_chunk@4"}
