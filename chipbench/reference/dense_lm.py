"""Plain float32 reference of a dense GQA decoder (Qwen2 architecture).

Pre-norm RMSNorm, attention with QKV bias and rotary embeddings on split
halves, SwiGLU MLP, tied or untied head, as the published ``config.json``
describes it.  One ``lax.scan`` over the stacked layers, each layer cast
to float32 inside the scan body, and every contraction at
``Precision.HIGHEST``.  It reads only the parameter tree and the
configuration's ``"model"`` group.

``low=True`` is the control: every matrix-product operand, weights and
activations alike, is rounded to float8 (e4m3) first, the precision below
the configuration's bfloat16.

The training reference (``loss``) is the mean next-token cross-entropy of
the same forward pass at every position.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _mm(spec, a, b, low):
    if low:
        a = a.astype(jnp.float8_e4m3fn).astype(F32)
        b = b.astype(jnp.float8_e4m3fn).astype(F32)
    return jnp.einsum(spec, a, b, precision=HI)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, positions, theta):
    """x: (B, S, H, D); rotate the two halves of D by position angles."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / x.shape[-1])
    ang = positions[:, :, None, None].astype(F32) * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def _layer(m, x, p, positions, low):
    B, S, d = x.shape
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // nq
    eps = m["rms_norm_eps"]
    a = p["attn"]
    h = _norm(x, p["mixer_norm"], eps)
    q = _mm("bsd,de->bse", h, a["wq"], low) + a["bq"]
    k = _mm("bsd,de->bse", h, a["wk"], low) + a["bk"]
    v = _mm("bsd,de->bse", h, a["wv"], low) + a["bv"]
    q = _rope(q.reshape(B, S, nq, hd), positions, m["rope_theta"])
    k = _rope(k.reshape(B, S, nkv, hd), positions, m["rope_theta"])
    v = v.reshape(B, S, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=2)          # query head i -> kv i // g
    v = jnp.repeat(v, nq // nkv, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, low) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, low)
    x = x + _mm("bse,ed->bsd", o.reshape(B, S, nq * hd), a["wo"], low)
    mp = p["mlp"]
    h = _norm(x, p["ffn_norm"], eps)
    gate = _mm("bsd,df->bsf", h, mp["w_gate"], low)
    up = _mm("bsd,df->bsf", h, mp["w_up"], low)
    return x + _mm("bsf,fd->bsd", gate * jax.nn.sigmoid(gate) * up,
                   mp["w_down"], low)


def hidden(m, params, tokens, low=False):
    """Final-norm hidden states ``(B, S, d)`` in float32."""
    B, S = tokens.shape
    x = params["embed"]["table"][tokens].astype(F32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    (stack,) = params["stack"]

    def body(x, layer):
        layer = jax.tree.map(lambda w: w.astype(F32), layer)
        return _layer(m, x, layer, positions, low), None

    x, _ = jax.lax.scan(body, x, stack)
    return _norm(x, params["final_norm"].astype(F32), m["rms_norm_eps"])


def head(m, params, x, low=False):
    emb = params["embed"]
    w = emb["lm_head"] if "lm_head" in emb else emb["table"].T
    return _mm("...d,dv->...v", x, w.astype(F32), low)[
        ..., :m["vocab_size"]]


def logits_at(m, params, tokens, at, low=False):
    """Logits ``(B, K, vocab)`` at positions ``at`` ``(B, K)``; causal, so
    right padding past a row's positions does not change them."""
    x = hidden(m, params, tokens, low)
    x = jnp.take_along_axis(x, at[:, :, None], axis=1)
    return head(m, params, x, low)


def loss(m, params, tokens, targets, low=False):
    """Mean next-token cross-entropy over every position."""
    logits = head(m, params, hidden(m, params, tokens, low), low)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
