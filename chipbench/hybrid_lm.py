"""The Jamba configuration as the program takes it.

A configuration file keeps the model's published ``config.json`` keys
at its top level (``model_type`` "jamba"), beside ``name``, ``driver``,
``source``, ``reduced``, ``assumed`` and ``engine``, so that each key sits
where the published config puts it; :func:`arch_config` maps them onto
the program's ``ArchConfig`` (Mamba-1 mixers with RMSNorm on dt, B
and C, attention with no positional encoding where ``layer %
attn_layer_period == attn_layer_offset``, SwiGLU MLP, RMSNorm), and
:func:`make_model` builds the program's model from it.  Only dense MLPs
(``num_experts`` 1) and Mamba's own ``dt_rank`` rule (``ceil(hidden_size /
16)``) are mapped.
"""
from __future__ import annotations

#: published key -> the program's ArchConfig field
KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attn_layer_period": "attn_period",
    "attn_layer_offset": "attn_offset",
    "mamba_d_state": "ssm_state",
    "mamba_d_conv": "ssm_conv",
    "mamba_expand": "ssm_expand",
}

#: published keys the program's layers hard-wire, with the value they take
FIXED = {"model_type": "jamba", "hidden_act": "silu", "num_experts": 1,
         "mamba_conv_bias": True, "mamba_proj_bias": False,
         "sliding_window": None}


def arch_config(cfg: dict):
    from repro.models.config import ArchConfig
    m = cfg
    for key, want in FIXED.items():
        if m.get(key) != want:
            raise ValueError(f"{cfg['name']}: {key} is {m.get(key)!r}; the "
                             f"program's Jamba layers take {want!r}")
    rank = -(-m["hidden_size"] // 16)
    if m.get("mamba_dt_rank") != rank:
        raise ValueError(f"{cfg['name']}: mamba_dt_rank is "
                         f"{m.get('mamba_dt_rank')!r}; the program's Mamba "
                         f"layers take ceil(hidden_size / 16) = {rank}")
    kw = {field: m[key] for key, field in KEYS.items()}
    return ArchConfig(name=cfg["name"], family="hybrid", rope=False,
                      ssm_inner_norm=True,
                      dtype=m.get("torch_dtype", "bfloat16"), **kw)


def make_model(cfg: dict, **kw):
    from repro.models import factory
    return factory.make_model(arch_config(cfg), **kw)
