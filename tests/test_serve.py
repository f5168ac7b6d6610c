"""Serving engine tests."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.models.factory import make_model
from repro.serve.engine import ServeEngine, sample_logits

CFG = ARCHS["qwen2.5-3b"].reduced()
KEY = jax.random.PRNGKey(0)


def test_greedy_generation_deterministic():
    model = make_model(CFG, moe_impl="dense")
    params = model.init(KEY)
    engine = ServeEngine(model=model, params=params, max_len=48)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                CFG.vocab_size)
    out1 = engine.generate(prompt, 8)
    out2 = engine.generate(prompt, 8)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert out1.shape == (2, 8)


def test_generation_matches_teacher_forcing():
    """Greedy decode through the cache == greedy argmax of the full
    forward pass fed its own outputs (cache consistency end-to-end)."""
    model = make_model(CFG, moe_impl="dense")
    params = model.init(KEY)
    engine = ServeEngine(model=model, params=params, max_len=32)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0,
                                CFG.vocab_size)
    gen = np.asarray(engine.generate(prompt, 6))
    # teacher-forced replay
    toks = np.asarray(prompt)
    for i in range(6):
        logits, _ = model.forward(params, {"tokens": jnp.asarray(toks)})
        nxt = int(jnp.argmax(logits[0, -1]))
        assert nxt == int(gen[0, i]), (i, nxt, gen)
        toks = np.concatenate([toks, [[nxt]]], axis=1)


def test_generate_zero_new_tokens():
    """Regression: n_new=0 used to crash on jnp.concatenate of an empty
    list; it must return an empty (B, 0) continuation instead."""
    model = make_model(CFG, moe_impl="dense")
    params = model.init(KEY)
    engine = ServeEngine(model=model, params=params, max_len=32)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                                CFG.vocab_size)
    out = engine.generate(prompt, 0)
    assert out.shape == (2, 0)
    assert out.dtype == prompt.dtype


def test_generate_eos_padding():
    """With eos_id=, a sequence that samples eos stops contributing sampled
    tokens: the eos is kept and every later position is eos padding, while
    sequences that never sample eos are unchanged."""
    model = make_model(CFG, moe_impl="dense")
    params = model.init(KEY)
    engine = ServeEngine(model=model, params=params, max_len=32)
    prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0,
                                CFG.vocab_size)
    ref = np.asarray(engine.generate(prompt, 6))
    eos = int(ref[0, 2])                       # row 0 finishes at index 2
    out = np.asarray(engine.generate(prompt, 6, eos_id=eos))
    assert out.shape == ref.shape
    for b in range(2):
        row = list(ref[b])
        j = row.index(eos) if eos in row else None
        if j is None:
            np.testing.assert_array_equal(out[b], ref[b])
        else:
            np.testing.assert_array_equal(out[b, :j + 1], ref[b, :j + 1])
            assert (out[b, j:] == eos).all()   # padded after (and with) eos


def test_sample_logits_temperature():
    logits = jnp.asarray([[[0.0, 10.0, 0.0]]])
    assert int(sample_logits(logits, KEY, 0.0)[0, 0]) == 1
    draws = {int(sample_logits(logits, jax.random.PRNGKey(i), 5.0)[0, 0])
             for i in range(50)}
    assert len(draws) > 1          # high temperature actually samples


def test_audio_decode_step():
    cfg = ARCHS["musicgen-medium"].reduced()
    model = make_model(cfg)
    params = model.init(KEY)
    caches = model.init_caches(2, 16)
    batch = {"frame_embeds": jnp.zeros((2, 1, cfg.frontend_dim),
                                       jnp.float32)}
    logits, _ = jax.jit(model.decode_step)(params, caches, batch,
                                           jnp.asarray(0, jnp.int32))
    assert logits.shape == (2, 1, cfg.n_codebooks, cfg.vocab_size)
