"""Training substrate: data determinism, optimizer, microbatching,
checkpoint/restore fault tolerance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models.config import ShapeConfig
from repro.models.factory import make_inputs, make_model
from repro.train import checkpoint as ckpt
from repro.train.data import make_data
from repro.train.loop import make_train_step
from repro.train.optimizer import (AdamWConfig, adamw_init, adamw_update,
                                   compress_error_feedback, cosine_schedule,
                                   dequantize_int8, quantize_int8)

CFG = ARCHS["qwen2.5-3b"].reduced()
SHAPE = ShapeConfig("t", "train", 64, 8)
KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------------- data
def test_data_deterministic_and_stateless():
    d1 = make_data(CFG, SHAPE, seed=3)
    d2 = make_data(CFG, SHAPE, seed=3)
    b1, b2 = d1.batch(7), d2.batch(7)
    for a, b in zip(jax.tree.leaves(b1), jax.tree.leaves(b2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    b3 = d1.batch(8)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))


def test_data_tokens_in_vocab():
    batch = make_data(CFG, SHAPE).batch(0)
    assert int(batch["tokens"].max()) < CFG.vocab_size
    assert int(batch["tokens"].min()) >= 0


# -------------------------------------------------------------- optimizer
def test_cosine_schedule_shape():
    c = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    assert float(cosine_schedule(c, 0)) == pytest.approx(0.0)
    assert float(cosine_schedule(c, 10)) == pytest.approx(1e-3, rel=1e-2)
    assert float(cosine_schedule(c, 100)) == pytest.approx(1e-4, rel=1e-2)


def test_adamw_moves_params_downhill():
    params = {"w": jnp.ones((4, 4), jnp.float32)}
    state = adamw_init(params)
    grads = {"w": jnp.ones((4, 4), jnp.float32)}
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, weight_decay=0.0)
    new, state, m = adamw_update(cfg, grads, state, params)
    assert float(new["w"].mean()) < 1.0
    assert m["grad_norm"] == pytest.approx(4.0)


def test_quantize_roundtrip_error_feedback():
    tree = {"a": jnp.asarray(np.random.default_rng(0).normal(size=(64,)),
                             jnp.float32)}
    q, s = quantize_int8(tree)
    deq = dequantize_int8(q, s)
    err = float(jnp.max(jnp.abs(deq["a"] - tree["a"])))
    assert err <= float(s["a"]) * 0.5 + 1e-6
    # error feedback keeps the running sum unbiased
    residual = {"a": jnp.zeros((64,), jnp.float32)}
    q, s, res = compress_error_feedback(tree, residual)
    recon = jax.tree.map(lambda d, r: d + r, dequantize_int8(q, s), res)
    np.testing.assert_allclose(np.asarray(recon["a"]),
                               np.asarray(tree["a"]), atol=1e-5)


# ------------------------------------------------------------- train step
def test_loss_decreases():
    model = make_model(CFG, moe_impl="dense")
    params = model.init(KEY)
    opt = adamw_init(params)
    cfg = AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=40)
    step = jax.jit(make_train_step(model.loss, cfg))
    data = make_data(CFG, SHAPE)
    first = last = None
    for i in range(40):
        params, opt, m = step(params, opt, data.batch(i))
        if first is None:
            first = float(m.loss)
        last = float(m.loss)
    assert last < first - 0.1


def test_microbatch_equivalence():
    """n_micro=1 vs n_micro=4 produce (nearly) identical updates."""
    model = make_model(CFG, moe_impl="dense")
    params = model.init(KEY)
    batch = make_inputs(CFG, SHAPE, abstract=False)
    cfg = AdamWConfig(lr=1e-3, warmup_steps=0)
    p1, _, m1 = jax.jit(make_train_step(model.loss, cfg, n_micro=1))(
        params, adamw_init(params), batch)
    p4, _, m4 = jax.jit(make_train_step(model.loss, cfg, n_micro=4))(
        params, adamw_init(params), batch)
    assert float(m1.loss) == pytest.approx(float(m4.loss), rel=2e-2)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-3, rtol=5e-2)


# ------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    model = make_model(CFG, moe_impl="dense")
    params = model.init(KEY)
    ckpt.save(tmp_path, 12, {"params": params}, {"step": 12})
    assert ckpt.latest_step(tmp_path) == 12
    like = jax.eval_shape(lambda: {"params": params})
    restored, extra = ckpt.restore(tmp_path, 12, like)
    assert extra["step"] == 12
    for a, b in zip(jax.tree.leaves(params),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_cleanup_and_latest(tmp_path):
    tree = {"x": jnp.zeros((4,))}
    for s in (1, 2, 3, 4):
        ckpt.save(tmp_path, s, tree)
    ckpt.cleanup(tmp_path, keep_last=2)
    assert ckpt.steps(tmp_path) == [3, 4]


def test_async_checkpointer(tmp_path):
    tree = {"x": jnp.arange(8.0)}
    saver = ckpt.AsyncCheckpointer(tmp_path, keep_last=2)
    saver.save(5, tree, {"step": 5})
    saver.wait()
    restored, extra = ckpt.restore(tmp_path, 5, jax.eval_shape(lambda: tree))
    np.testing.assert_array_equal(np.asarray(restored["x"]),
                                  np.arange(8.0))


def test_restart_resumes_exact_stream(tmp_path):
    """Fault-tolerance contract: restore + deterministic data reproduce
    the uninterrupted run exactly."""
    from repro.launch.mesh import make_mesh
    from repro.launch.train import train
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig("t", "train", 32, 4)
    # uninterrupted run
    p_ref, hist_ref = train(CFG, shape, mesh, 9, ckpt_dir=None, log_every=1)
    # interrupted at 5, restart from checkpoint
    with pytest.raises(RuntimeError):
        train(CFG, shape, mesh, 9, ckpt_dir=tmp_path, ckpt_every=3,
              log_every=1, fail_at_step=5)
    p_resumed, hist = train(CFG, shape, mesh, 9, ckpt_dir=tmp_path,
                            ckpt_every=3, log_every=1)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_resumed)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)
