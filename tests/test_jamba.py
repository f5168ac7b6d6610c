"""Jamba (Mamba-1 + attention hybrid) on the paged serving path against
the plain float32 reference, at a small size on seeded random weights.

The model runs in float32 here, so model and reference differ only in
the order of their float32 sums: the tolerances below are a few hundred
float32 ulps of the values compared, and computing either side in
bfloat16 (8 mantissa bits) would miss them by orders of magnitude.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import blocks, mamba, reference
from repro.models.config import ArchConfig
from repro.models.factory import make_model
from repro.serve import PagedContinuousEngine, Request

ROOT = Path(__file__).resolve().parent.parent
#: Jamba's layout at a small width: mamba at even layers, attention (one
#: KV head, no RoPE) at odd ones, RMSNorm on dt/B/C, dt_rank ceil(d/16),
#: a dense SwiGLU MLP everywhere, tied head
CFG = ArchConfig(
    name="jamba-tiny", family="hybrid", n_layers=4, d_model=64, n_heads=4,
    n_kv_heads=1, d_ff=128, vocab_size=256, ssm_state=8, ssm_conv=4,
    ssm_expand=2, ssm_inner_norm=True, rope=False,
    attn_period=2, attn_offset=1, norm_eps=1e-6, tie_embeddings=True,
    dtype="float32")
BS = 8                       # block size = prefill chunk
MAX_LEN = 48
N_NEW = 9                    # one token from the prefill, 8 decode steps
#: logits, as a share of the largest reference logit: model and reference
#: read 1.1e-6 to 1.5e-6 here (float32 sums in another order through 4
#: layers and up to 37 scan steps); the same model in bfloat16 reads 0.04,
#: and a dropped Jamba term 0.5 to 0.8 (test_departures_matter)
LOGIT_TOL = 1e-4
#: recurrent state: one scan over the prompt against the same scan cut
#: into chunks — the same float32 operations, reassociated at most
STATE_TOL = 1e-5


def _perturb(params, key):
    """Norm scales and D at 1 + 0.1 N(0,1), biases at 0.1 N(0,1): every
    term of a layer then moves the output."""
    paths, tree = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(paths):
        name = str(getattr(path[-1], "key", path[-1]))
        z = jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
        if name.endswith("norm") or name == "D":
            leaf = leaf + 0.1 * z.astype(leaf.dtype)
        elif name in ("conv_b", "bq", "bk", "bv"):
            leaf = 0.1 * z.astype(leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture(scope="module")
def model_params():
    model = make_model(CFG)
    return model, _perturb(model.init(jax.random.PRNGKey(0)),
                           jax.random.PRNGKey(1))


def _prompt(n, seed=3):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         CFG.vocab_size), np.int32)


def _serve(model, params, prompt):
    """Serve one request on the paged engine; returns its tokens, the
    logits each was sampled from and the engine."""
    eng = PagedContinuousEngine(model=model, params=params, n_slots=2,
                                max_len=MAX_LEN, block_size=BS)
    seen = []
    sample = eng._sample

    def record(logits, key):
        seen.append(np.asarray(logits)[0, 0])      # slot 0 / the prefill
        return sample(logits, key)
    eng._sample = record
    (out,) = eng.run([(prompt, N_NEW)])
    return out, np.stack(seen), eng


def _scale(x):
    return float(np.abs(x).max())


@pytest.mark.parametrize("n", [1, BS - 1, BS + 1, 3 * BS + 5])
def test_chunked_prefill_then_decode_matches_reference(model_params, n):
    """Prompts of 1, chunk-1, chunk+1 and 3 chunks + 5 tokens through the
    chunk step, then 8 decode steps: every sampled row of logits matches
    the reference's full forward pass over prompt + served tokens."""
    model, params = model_params
    prompt = _prompt(n)
    out, got, eng = _serve(model, params, prompt)
    assert eng.stats.prefills_by_bucket == {f"prefill_chunk@{BS}":
                                            -(-n // BS)}
    seq = np.concatenate([prompt, out[:-1]])[None]
    want = np.asarray(reference.hybrid_reference_logits(
        CFG, params, jnp.asarray(seq)))[0, n - 1:]
    assert got.shape == want.shape == (N_NEW, CFG.vocab_size)
    err = np.abs(got - want).max() / _scale(want)
    assert err < LOGIT_TOL, err


@pytest.mark.parametrize("n", [BS - 1, 2 * BS, 3 * BS + 5])
def test_chunked_state_equals_full_scan(model_params, n):
    """The slot's recurrent state after a chunked prefill equals the
    state of one scan over the whole prompt (``model.prefill``)."""
    model, params = model_params
    prompt = _prompt(n, seed=5)
    eng = PagedContinuousEngine(model=model, params=params, n_slots=2,
                                max_len=MAX_LEN, block_size=BS)
    eng._prefill_into_slot(Request(tokens=prompt, max_new_tokens=1), 1)
    _, full = model.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                            max_len=MAX_LEN)
    mamba_at = [i for i, s in enumerate(blocks.layer_pattern(CFG))
                if s.mixer == "mamba"]
    assert mamba_at
    for i in mamba_at:
        for got, want in zip(eng._dense[i], full[i]):
            got = np.asarray(got[:, 1], np.float32)
            want = np.asarray(want[:, 0], np.float32)
            assert np.abs(got - want).max() <= STATE_TOL * _scale(want)


@pytest.mark.parametrize("departure", ["ssm_inner_norm", "rope"])
def test_departures_matter(model_params, departure):
    """Each Jamba term is checked: the model with it turned off (no
    RMSNorm on dt/B/C; RoPE on) misses the reference by far more than
    the tolerance, which the model with it on meets."""
    model, params = model_params
    toks = jnp.asarray(_prompt(3 * BS + 5, seed=7)[None])
    want = np.asarray(reference.hybrid_reference_logits(CFG, params, toks))
    off = CFG.replace(**{departure: not getattr(CFG, departure)})
    for cfg, close in ((CFG, True), (off, False)):
        got, _ = make_model(cfg).forward(params, {"tokens": toks})
        err = np.abs(np.asarray(got) - want).max() / _scale(want)
        assert (err < LOGIT_TOL) == close, (cfg.name, departure, err)
        if not close:
            assert err > 100 * LOGIT_TOL


def test_config_file_holds_the_catalog_keys():
    """``chipbench/configs/jamba2-3b.json`` holds the published config's
    26 keys with their values, unchanged and uncut, at its top level
    (where the catalog's keys are looked up), and maps to attention at
    layers 7 and 21 only, a Mamba mixer and a dense MLP elsewhere."""
    from chipbench import hybrid_lm
    cfg = json.loads((ROOT / "chipbench/configs/jamba2-3b.json")
                     .read_text())
    own = {"name", "driver", "source", "reduced", "assumed", "engine"}
    assert {k: v for k, v in cfg.items() if k not in own} == {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    assert cfg["reduced"] == []
    arch = hybrid_lm.arch_config(cfg)
    specs = blocks.layer_specs(arch)
    assert [i for i, s in enumerate(specs) if s.mixer == "attn"] == [7, 21]
    assert {s.mixer for s in specs} == {"attn", "mamba"}
    assert {s.ffn for s in specs} == {"dense"}
    assert (arch.d_inner, mamba.dt_rank(arch), arch.resolved_head_dim) == \
        (5120, 160, 128)
    assert not arch.rope and arch.ssm_inner_norm


@pytest.mark.parametrize("key,value", [
    ("mamba_dt_rank", 128),          # not ceil(hidden_size / 16)
    ("num_experts", 16),             # sparse MLPs are not mapped
    ("mamba_proj_bias", True),
])
def test_config_the_layers_cannot_take_is_refused(key, value):
    """A published key the program's Jamba layers would silently ignore
    is refused at set-up instead."""
    from chipbench import hybrid_lm
    cfg = json.loads((ROOT / "chipbench/configs/jamba2-3b.json")
                     .read_text())
    cfg[key] = value
    with pytest.raises(ValueError, match=key):
        hybrid_lm.arch_config(cfg)
