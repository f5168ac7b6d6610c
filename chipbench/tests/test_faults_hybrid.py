"""The Jamba serving cell on the CPU at a small size: a dry run reads
``correct`` true, each fault planted under the timed path (the recurrent
state reset at each chunk boundary, a padded chunk tail folded into the
state, an altered served token) turns it false, and the float8 control
reads above the limit."""
import jax
import jax.numpy as jnp
import pytest

from conftest import dry_run

CELL = "serve.jamba2-3b.longdoc"
#: a Jamba-shaped model small enough for the CPU, in float32: mamba at
#: even layers, attention at odd ones
TINY_JAMBA = {
    "attn_layer_offset": 1, "attn_layer_period": 2,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 8,
    "mamba_dt_rank": 4, "mamba_expand": 2, "mamba_proj_bias": False,
    "model_type": "jamba", "num_attention_heads": 4, "num_experts": 1,
    "num_hidden_layers": 4, "num_key_value_heads": 1, "rms_norm_eps": 1e-6,
    "sliding_window": None, "tie_word_embeddings": True, "vocab_size": 512,
    "torch_dtype": "float32"}
#: prompts of 4 to 64 tokens at block 16: most take two chunks or more,
#: and the last chunk is padded
TINY = {
    "config": dict(TINY_JAMBA,
                   engine={"n_slots": 4, "max_len": 96, "block_size": 16}),
    "rate": 3.0,
    "prompt": {"kind": "lognormal", "median": 24, "sigma": 0.8, "lo": 4,
               "hi": 64},
    "output": {"kind": "lognormal", "median": 8, "sigma": 0.7, "lo": 2,
               "hi": 32},
    "check_requests": 8}


def _failed(line, name="served_gap_std"):
    chk = line["checks"][name]
    return not line["correct"] and chk["value"] > chk["limit"]


def _engine_patch(fn):
    def patch(drv):
        fn(drv.engine)
    return patch


def test_dry_run():
    kept = []
    line, drv = dry_run(CELL, TINY, seconds=3.0,
                        patch=lambda d: kept.append(d.engine))
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}
    assert line["attempted"] == 9 and line["failed"] == 0
    assert line["checks"]["served_gap_std"]["value"] < 1e-3
    chunks = kept[0].stats.prefills_by_bucket
    assert set(chunks) == {"prefill_chunk@16"}
    assert chunks["prefill_chunk@16"] > drv.attempted + 1   # multi-chunk
    adm = drv.ctx.counters["admissions"]
    assert sorted(a[2] for a in adm) == sorted(len(r.prompt)
                                               for r in drv.reqs)


def test_state_reset_at_each_chunk():
    """Every chunk starts from a zero recurrent state."""
    def reset(e):
        step = e._prefill_chunk

        def fresh(params, pools, dense, table, slot, tok, pos, last):
            dense = [None if d is None else jax.tree.map(
                lambda x: x.at[:, slot].set(0), d) for d in dense]
            return step(params, pools, dense, table, slot, tok, pos, last)
        e._prefill_chunk = fresh
    line, _ = dry_run(CELL, TINY, seconds=3.0, patch=_engine_patch(reset))
    assert _failed(line)


def test_padded_tail_folded_into_state(monkeypatch):
    """The chunk step folds the padded tail of a prompt's last chunk into
    the recurrent state."""
    from repro.models import mamba
    monkeypatch.setattr(mamba, "mask_tail", lambda dt, x, n_valid: (dt, x))
    line, _ = dry_run(CELL, TINY, seconds=3.0)
    assert _failed(line)


def test_token_altered():
    def alter(e):
        sample = e._sample

        def bad(logits, key):
            tok = sample(logits, key)
            return tok.at[0].set((tok[0] + 1) % logits.shape[-1])
        e._sample = bad
    line, _ = dry_run(CELL, TINY, seconds=3.0, patch=_engine_patch(alter))
    assert _failed(line)


#: a bfloat16 Jamba small enough for the CPU, with outputs long enough that
#: a few hundred served tokens are compared
CONTROL_SIZE = dict(
    TINY,
    config=dict(TINY_JAMBA, hidden_size=256, intermediate_size=512,
                vocab_size=4096, mamba_dt_rank=16, torch_dtype="bfloat16",
                engine={"n_slots": 4, "max_len": 160, "block_size": 16}),
    output={"kind": "lognormal", "median": 32, "sigma": 0.5, "lo": 8,
            "hi": 64},
    check_requests=12)


def test_control():
    """At this size (seed 1) the program in bfloat16 reads 0.08 std and
    the float8 control 1.36: the control comes out not correct against
    the cell's limit, set from readings at the cell's own size on the
    chip (PERF.md §2)."""
    line, drv = dry_run(CELL, CONTROL_SIZE, seconds=3.0, seed=1)
    limit = drv.tr["limits"]["served_gap_std"]
    assert line["correct"]
    assert line["checks"]["served_gap_std"]["value"] < limit / 3
    assert drv.reference_gaps(drv.sample(), low=True)["gap_std"] > limit
