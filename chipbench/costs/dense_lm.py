"""Operations and bytes of a dense GQA decoder, from its published sizes.

``m`` is the ``"model"`` group of a configuration file (the model's
``config.json`` keys).  A multiply-add counts as two operations.
"""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Parameters that enter a matrix product per token: the projections
    of every layer and the head (the embedding gather is not one)."""
    d, f = m["hidden_size"], m["intermediate_size"]
    hd = d // m["num_attention_heads"]
    q = m["num_attention_heads"] * hd
    kv = m["num_key_value_heads"] * hd
    layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return m["num_hidden_layers"] * layer + d * m["vocab_size"]


def all_params(m: dict) -> int:
    """Every parameter, biases and norms included."""
    d, v = m["hidden_size"], m["vocab_size"]
    hd = d // m["num_attention_heads"]
    q = m["num_attention_heads"] * hd
    kv = m["num_key_value_heads"] * hd
    per_layer_extra = q + 2 * kv + 2 * d       # biases and two norms
    emb = d * v * (1 if m["tie_word_embeddings"] else 2)
    return (matmul_params(m) - d * v + emb
            + m["num_hidden_layers"] * per_layer_extra + d)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    hd = m["hidden_size"] // m["num_attention_heads"]
    return 2 * m["num_hidden_layers"] * m["num_key_value_heads"] * hd \
        * itemsize


def forward_flops(m: dict, ctx_sum: float, tokens: float) -> float:
    """Operations of a forward pass over ``tokens`` tokens whose attended
    context lengths sum to ``ctx_sum``: two per matrix-product parameter
    per token, plus the score and value products of attention (four per
    head dimension per attended position)."""
    d = m["hidden_size"]
    return 2.0 * matmul_params(m) * tokens \
        + 4.0 * m["num_hidden_layers"] * d * ctx_sum

