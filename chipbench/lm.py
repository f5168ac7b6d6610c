"""The language-model configuration as the program takes it.

A configuration file keeps the model's published ``config.json`` keys
under ``"model"``; :func:`arch_config` maps them onto the program's
``ArchConfig`` (dense GQA decoder with QKV bias, SwiGLU, RMSNorm, rotary
embeddings), and :func:`make_model` builds the program's model from it.
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
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def arch_config(cfg: dict):
    from repro.models.config import ArchConfig
    m = cfg["model"]
    if m.get("hidden_act", "silu") != "silu":
        raise ValueError(f"unsupported activation {m['hidden_act']!r}")
    kw = {field: m[key] for key, field in KEYS.items()}
    return ArchConfig(name=cfg["name"], family="dense", qkv_bias=True,
                      dtype=m.get("torch_dtype", "bfloat16"), **kw)


def make_model(cfg: dict, **kw):
    from repro.models import factory
    return factory.make_model(arch_config(cfg), **kw)

