"""Shared neural layers: RMSNorm, RoPE, GQA attention (train + decode),
gated MLPs.  Pure-functional: params are nested dicts of jnp arrays."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..compat import mesh_in_context
from .config import ArchConfig


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


# --------------------------------------------------------------------- norms
def rms_norm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale


# ---------------------------------------------------------------------- RoPE
def rope_frequencies(head_dim: int, theta: float):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention
def _pad_heads_cols(w, nq, nq_pad, hd, nkv, axis=1):
    """Zero-pad per-KV-GROUP head blocks from nq to nq_pad heads (§Perf
    B3).  Group-major layout (head = kv * g + j) is preserved, so GQA
    grouping is unchanged; padded lanes are exact zero-saddles (their wo
    rows are also zero => zero gradients, unchanged function)."""
    if nq_pad == nq:
        return w
    nkv = max(nkv, 1)
    g, g_pad = nq // nkv, nq_pad // nkv
    if axis == 1:                           # (d, nq*hd) columns
        d = w.shape[0]
        grouped = w.reshape(d, nkv, g, hd)
        pad = jnp.zeros((d, nkv, g_pad - g, hd), w.dtype)
        return jnp.concatenate([grouped, pad], axis=2).reshape(
            d, nq_pad * hd)
    d = w.shape[1]                          # (nq*hd, d) rows (wo)
    grouped = w.reshape(nkv, g, hd, d)
    pad = jnp.zeros((nkv, g_pad - g, hd, d), w.dtype)
    return jnp.concatenate([grouped, pad], axis=1).reshape(nq_pad * hd, d)


def init_attention(cfg: ArchConfig, key) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    nq_pad = cfg.padded_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    dt = _dtype(cfg)
    wo = _pad_heads_cols(
        jax.random.normal(k4, (nq * hd, d), dt) * (s / math.sqrt(cfg.n_layers)),
        nq, nq_pad, hd, nkv, axis=0)
    if cfg.fused_proj:
        # one column-parallel matmul for q|k|v: its transpose in backward
        # produces ONE dx all-reduce instead of three (§Perf A2)
        wq = _pad_heads_cols(jax.random.normal(k1, (d, nq * hd), dt) * s,
                             nq, nq_pad, hd, nkv)
        kv = jax.random.normal(k2, (d, 2 * nkv * hd), dt) * s
        p = {"wqkv": jnp.concatenate([wq, kv], axis=1), "wo": wo}
        if cfg.qkv_bias:
            p["bqkv"] = jnp.zeros(((nq_pad + 2 * nkv) * hd,), dt)
        return p
    p = {
        "wq": _pad_heads_cols(jax.random.normal(k1, (d, nq * hd), dt) * s,
                              nq, nq_pad, hd, nkv),
        "wk": jax.random.normal(k2, (d, nkv * hd), dt) * s,
        "wv": jax.random.normal(k3, (d, nkv * hd), dt) * s,
        "wo": wo,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq_pad * hd,), dt)
        p["bk"] = jnp.zeros((nkv * hd,), dt)
        p["bv"] = jnp.zeros((nkv * hd,), dt)
    return p


def _project_qkv(p, x, cfg: ArchConfig, positions):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    nq = cfg.padded_heads
    if "wqkv" in p:
        qkv = x @ p["wqkv"]
        if cfg.qkv_bias:
            qkv = qkv + p["bqkv"]
        q, k, v = jnp.split(
            qkv, [nq * hd, (nq + cfg.n_kv_heads) * hd], axis=-1)
    else:
        q = x @ p["wq"]
        k = x @ p["wk"]
        v = x @ p["wv"]
        if cfg.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, nq, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_attention(q, k, v, causal: bool = True, kv_positions=None,
                  q_positions=None):
    """Grouped-query attention.  q: (B,S,Hq,D), k/v: (B,T,Hkv,D)."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, S, Hkv, g, D)
    scores = jnp.einsum("bshgd,bthd->bhgst", qg, k) / math.sqrt(D)
    if causal:
        if q_positions is None:
            q_positions = jnp.arange(S)
        if kv_positions is None:
            kv_positions = jnp.arange(T)
        mask = q_positions[:, None] >= kv_positions[None, :]
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v)
    return out.reshape(B, S, Hq * D)


#: Sequence length above which the pure-JAX blockwise (flash-style) path is
#: used instead of materializing the full (S, T) score matrix.
CHUNKED_ATTN_THRESHOLD = 2048


def chunked_attention(q, k, v, causal: bool = True,
                      q_block: int = 1024, kv_block: int = 1024):
    """Blockwise streaming-softmax attention (pure-JAX flash oracle).

    q: (B, S, Hq, D); k/v: (B, T, Hkv, D).  Never materializes more than a
    (B, Hkv, g, q_block, kv_block) score tile; the running (max, denom, acc)
    carry is the standard online-softmax recurrence.  This is both the
    memory-sane model path for 32k+ sequences and the oracle the Pallas
    flash kernel is validated against.
    """
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qb = math.gcd(q_block, S)
    kb = math.gcd(kv_block, T)
    nq, nk = S // qb, T // kb

    qg = q.reshape(B, nq, qb, Hkv, g, D).astype(jnp.float32)
    kc = k.reshape(B, nk, kb, Hkv, D).astype(jnp.float32)
    vc = v.reshape(B, nk, kb, Hkv, D).astype(jnp.float32)
    scale = 1.0 / math.sqrt(D)

    def q_block_fn(qi, qblk):
        # qblk: (B, qb, Hkv, g, D)
        m0 = jnp.full((B, Hkv, g, qb), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, g, qb), jnp.float32)
        a0 = jnp.zeros((B, Hkv, g, qb, D), jnp.float32)

        def kv_step(carry, inputs):
            m, l, acc = carry
            ki, kblk, vblk = inputs
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kblk) * scale
            if causal:
                qpos = qi * qb + jnp.arange(qb)
                kpos = ki * kb + jnp.arange(kb)
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows (m_new == -inf)
            safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - safe_m[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] \
                + jnp.einsum("bhgqk,bkhd->bhgqd", p, vblk)
            return (m_new, l, acc)

        ks = jnp.arange(nk)
        (m, l, acc), _ = jax.lax.scan(
            lambda c, i: (kv_step(c, i), None), (m0, l0, a0),
            (ks, kc.swapaxes(0, 1), vc.swapaxes(0, 1)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]      # (B, Hkv, g, qb, D)
        return out.transpose(0, 3, 1, 2, 4)                # (B, qb, Hkv, g, D)

    outs = jax.lax.map(lambda i: q_block_fn(i, qg[:, i]), jnp.arange(nq))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, Hq * D)
    return out.astype(q.dtype)


def _expand_and_pin_heads(q, k, v, cfg: ArchConfig):
    """§Perf B2: tile KV to the full query-head count and pin the head dim
    to the model axis, so every blockwise-attention einsum is rank-local.

    Without this, GSPMD splits the head_dim contraction across the ranks
    sharing a kv head (kv_heads < model size) and inserts an all-reduce of
    the score tile at EVERY (q-block, kv-block) step — the dominant wire
    cost for GQA archs at 32k context.  The cost here is (pad + replicate)
    KV memory and ~(pad/heads) idle compute, both small."""
    from jax.sharding import PartitionSpec as P
    g = cfg.padded_heads // max(cfg.n_kv_heads, 1)
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    # (B4 — constraining the pre-expansion K/V to replicated instead was
    # tried and REFUTED: GSPMD propagated the replication into the
    # surrounding layer and wire went up 49%; see EXPERIMENTS.md §Perf.)
    if not mesh_in_context():
        return q, k, v          # single device: nothing to pin
    spec = P(None, None, "model", None)
    q = jax.lax.with_sharding_constraint(q, spec)
    k = jax.lax.with_sharding_constraint(k, spec)
    v = jax.lax.with_sharding_constraint(v, spec)
    return q, k, v


def attention_block(p, x, cfg: ArchConfig, positions=None, use_kernel=False):
    """Full-sequence (training / prefill) attention."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, x, cfg, positions)
    if use_kernel:
        from ..kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=True)
        out = out.reshape(B, S, -1)
    elif S > CHUNKED_ATTN_THRESHOLD:
        if cfg.attn_expand_kv:
            q, k, v = _expand_and_pin_heads(q, k, v, cfg)
        out = chunked_attention(q, k, v, causal=True)
    else:
        out = gqa_attention(q, k, v, causal=True)
    return out @ p["wo"]


def attention_prefill(p, x, cfg: ArchConfig, positions=None):
    """Training-shape attention that also returns the (k, v) cache."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, x, cfg, positions)
    if S > CHUNKED_ATTN_THRESHOLD:
        if cfg.attn_expand_kv:
            qe, ke, ve = _expand_and_pin_heads(q, k, v, cfg)
            out = chunked_attention(qe, ke, ve, causal=True)
        else:
            out = chunked_attention(q, k, v, causal=True)
    else:
        out = gqa_attention(q, k, v, causal=True)
    return out @ p["wo"], k, v


def attention_decode(p, x, cfg: ArchConfig, cache_k, cache_v, pos):
    """Decode step with a pre-filled KV cache.

    x: (B, S, d) — S = 1 for ordinary decode, S > 1 for a chunked-prefill
    step that processes S prompt tokens at once; cache_k/v: (B, S_max,
    Hkv, D); pos: scalar index of the FIRST new token (the chunk covers
    positions pos .. pos + S - 1).  Returns (out, cache_k, cache_v).
    """
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(pos + jnp.arange(S)[None, :], (B, S))
    q, k, v = _project_qkv(p, x, cfg, positions)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, pos, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, pos, axis=1)
    T = cache_k.shape[1]
    kv_pos = jnp.arange(T)
    out = gqa_attention(q, cache_k, cache_v, causal=True,
                        kv_positions=kv_pos, q_positions=positions[0])
    return out @ p["wo"], cache_k, cache_v


def attention_decode_paged(p, x, cfg: ArchConfig, pool, layer, tables, pos):
    """One decode token per slot against a paged KV pool.

    x: (B, 1, d); pool: {"k", "v": (n_layers, n_pool_blocks, Hkv,
    block_size, D)}, stacked over the layer blocks; layer: this layer's
    index in it; tables: (B, max_blocks) pool block ids; pos: (B,) each
    slot's write index.  Writes every slot's new K/V row at ``(table[pos //
    block_size], pos % block_size)`` in place, then attends positions
    ``0 .. pos`` through the block table (``kernels/paged_attention``).
    Returns (out, pool)."""
    from ..kernels.paged_attention import ops as pa_ops
    B = x.shape[0]
    q, k, v = _project_qkv(p, x, cfg, pos[:, None])
    bs = pool["k"].shape[3]
    blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)
    head = jnp.arange(cfg.n_kv_heads)[None, :]
    off = (pos % bs)[:, None]
    with jax.named_scope("kv_scatter"):
        # one D-row per (slot, head); inactive slots write row 0 of the
        # null block 0, never attended by an active slot
        pool = {"k": pool["k"].at[layer, blk, head, off].set(k[:, 0]),
                "v": pool["v"].at[layer, blk, head, off].set(v[:, 0])}
    qg = q[:, 0].reshape(B, cfg.n_kv_heads, -1, q.shape[-1])
    out = pa_ops.paged_attention(qg, pool["k"], pool["v"], tables, pos + 1,
                                 layer)
    return out.reshape(B, 1, -1) @ p["wo"], pool


# ---------------------------------------------------------------------- MLPs
def init_mlp(cfg: ArchConfig, key, d_ff: Optional[int] = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    dt = _dtype(cfg)
    s = 1.0 / math.sqrt(d)
    down = jax.random.normal(k3, (f, d), dt) \
        * (1.0 / math.sqrt(f) / math.sqrt(cfg.n_layers))
    if cfg.fused_proj:
        return {"w_gateup": jax.random.normal(k1, (d, 2 * f), dt) * s,
                "w_down": down}
    return {
        "w_gate": jax.random.normal(k1, (d, f), dt) * s,
        "w_up": jax.random.normal(k2, (d, f), dt) * s,
        "w_down": down,
    }


def mlp_block(p, x, cfg: ArchConfig):
    if "w_gateup" in p:
        gate, up = jnp.split(x @ p["w_gateup"], 2, axis=-1)
    else:
        gate, up = x @ p["w_gate"], x @ p["w_up"]
    act = jax.nn.gelu(gate, approximate=True) if cfg.mlp_act == "geglu" \
        else jax.nn.silu(gate)
    return (act * up) @ p["w_down"]


# ----------------------------------------------------------------- embedding
def init_embedding(cfg: ArchConfig, key) -> dict:
    """Table/head sized to ``padded_vocab`` so the vocab dim shards evenly
    (internvl2's 92553 pads to 92672); padding logits are masked in
    ``unembed``, padding rows are never gathered."""
    dt = _dtype(cfg)
    v = cfg.padded_vocab
    emb = jax.random.normal(key, (v, cfg.d_model), dt) * 0.02
    p = {"table": emb}
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(
            jax.random.fold_in(key, 1), (cfg.d_model, v), dt) \
            / math.sqrt(cfg.d_model)
    return p


def embed(p, tokens):
    return p["table"][tokens]


def unembed(p, x, vocab_size: Optional[int] = None):
    logits = x @ p["lm_head"] if "lm_head" in p else x @ p["table"].T
    v = logits.shape[-1]
    if vocab_size is not None and vocab_size < v:
        mask = jnp.arange(v) >= vocab_size
        logits = jnp.where(mask, jnp.asarray(-1e30, logits.dtype), logits)
    return logits
