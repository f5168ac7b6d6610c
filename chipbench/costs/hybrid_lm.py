"""Operations and bytes of a Jamba hybrid (Mamba-1 + attention, dense
MLPs), from its published sizes.

``m`` is a configuration file, which holds the model's ``config.json``
keys at its top level.  Attention sits where ``layer % attn_layer_period
== attn_layer_offset``, a Mamba mixer elsewhere, an MLP in every layer.
A multiply-add counts as two operations.
"""
from __future__ import annotations


def _dims(m: dict) -> dict:
    d = m["hidden_size"]
    n = m["num_hidden_layers"]
    n_attn = sum(i % m["attn_layer_period"] == m["attn_layer_offset"]
                 for i in range(n))
    return {"d": d, "di": m["mamba_expand"] * d, "N": m["mamba_d_state"],
            "K": m["mamba_d_conv"], "r": m["mamba_dt_rank"],
            "hd": d // m["num_attention_heads"],
            "nq": m["num_attention_heads"],
            "nkv": m["num_key_value_heads"],
            "f": m["intermediate_size"], "V": m["vocab_size"],
            "n_attn": n_attn, "n_mamba": n - n_attn, "n": n}


def matmul_params(m: dict) -> int:
    """Parameters that enter a matrix product per token: every mixer's
    and MLP's projections and the head (the embedding gather is not
    one)."""
    k = _dims(m)
    d, di = k["d"], k["di"]
    mamba = d * 2 * di + di * (k["r"] + 2 * k["N"]) + k["r"] * di + di * d
    q, kv = k["nq"] * k["hd"], k["nkv"] * k["hd"]
    attn = 2 * d * q + 2 * d * kv
    return k["n_mamba"] * mamba + k["n_attn"] * attn \
        + k["n"] * 3 * d * k["f"] + d * k["V"]


def all_params(m: dict) -> int:
    """Every parameter: conv taps and bias, dt bias, A_log, D, the three
    inner norms, two norms a layer, the final norm, the (tied) table."""
    k = _dims(m)
    di = k["di"]
    mamba_extra = k["K"] * di + di + di + di * k["N"] + di \
        + k["r"] + 2 * k["N"]
    emb = k["d"] * k["V"] * (1 if m["tie_word_embeddings"] else 2)
    return (matmul_params(m) - k["d"] * k["V"] + emb
            + k["n_mamba"] * mamba_extra + k["n"] * 2 * k["d"] + k["d"])


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """Keys and values of one token in the attention layers."""
    k = _dims(m)
    return 2 * k["n_attn"] * k["nkv"] * k["hd"] * itemsize


def state_bytes_per_slot(m: dict, itemsize: int = 2) -> int:
    """One slot's recurrent state in all Mamba layers: the conv tail
    (``d_conv - 1`` inputs) in the serving type, the SSM state in
    float32."""
    k = _dims(m)
    return k["n_mamba"] * ((k["K"] - 1) * k["di"] * itemsize
                           + k["di"] * k["N"] * 4)


def scan_flops_per_token(m: dict) -> int:
    """Operations of one token's Mamba mixers outside the projections:
    the depthwise conv (2 per tap and channel) and the scan (exp(dt A),
    the decay, the input term and its add, and the 2-operation readout
    C . h: 6 per channel and state)."""
    k = _dims(m)
    return k["n_mamba"] * (2 * k["K"] * k["di"] + 6 * k["di"] * k["N"])


def forward_flops(m: dict, ctx_sum: float, tokens: float) -> float:
    """Operations of a forward pass over ``tokens`` tokens whose attended
    context lengths sum to ``ctx_sum``: two per matrix-product parameter
    per token, the scan's per token, and the score and value products of
    the attention layers (four per head dimension per attended
    position)."""
    k = _dims(m)
    return (2.0 * matmul_params(m) + scan_flops_per_token(m)) * tokens \
        + 4.0 * k["n_attn"] * k["nq"] * k["hd"] * ctx_sum


def chunk_least_s(m: dict, start: int, n: int, peaks: dict) -> float:
    """Least time of a prefill chunk of ``n`` real prompt tokens from
    position ``start``: the larger of its operations over the chip's peak
    and its bytes (every weight once, the keys and values it attends
    over, one slot's state read and written) over HBM bandwidth."""
    ctx = n * start + n * (n + 1) / 2.0
    ops = forward_flops(m, ctx, n)
    nbytes = 2 * all_params(m) + (start + n) * kv_bytes_per_token(m) \
        + 2 * state_bytes_per_slot(m)
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
