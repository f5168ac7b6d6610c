"""Plain float32 reference forward for the dense attention archs.

Written independently of ``layers``/``blocks``: one ``lax.scan`` over the
stacked layer params, each layer upcast to float32 inside the scan body
(so only one layer's float32 copy is live at a time) and every
contraction at ``Precision.HIGHEST``.  It reads the parameter tree the
model builds and nothing else of the model code, so agreement between
the two is evidence that the model — and the serving engines built on
it — compute the published architecture.

Scope: dense decoder stacks of pre-norm GQA attention (optional QKV bias,
rotary embeddings on split halves) and a gated MLP.  MoE, SSM, fused
projections, padded heads and modality frontends are rejected.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .config import ArchConfig

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _check_supported(cfg: ArchConfig) -> None:
    unsupported = {
        "MoE": cfg.n_experts, "SSM": cfg.ssm_state,
        "frontend": cfg.frontend, "fused_proj": cfg.fused_proj,
        "head padding": cfg.padded_heads != cfg.n_heads,
        "attention period": cfg.attn_period,
    }
    bad = [k for k, v in unsupported.items() if v]
    if bad or not cfg.n_heads:
        raise ValueError(f"reference forward covers dense attention stacks "
                         f"only; {cfg.name} has {bad or ['no attention']}")


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, positions, theta):
    """x: (B, S, H, D); rotate the two halves of D by position angles."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=_F32) * 2.0 / x.shape[-1])
    ang = positions[:, :, None, None].astype(_F32) * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def _layer(cfg: ArchConfig, x, p, positions):
    B, S, _ = x.shape
    hd, nq, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    a = p["attn"]
    h = _norm(x, p["mixer_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,de->bse", h, a["wq"], precision=_HI)
    k = jnp.einsum("bsd,de->bse", h, a["wk"], precision=_HI)
    v = jnp.einsum("bsd,de->bse", h, a["wv"], precision=_HI)
    if cfg.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(B, S, nq, hd), positions, cfg.rope_theta)
    k = _rope(k.reshape(B, S, nkv, hd), positions, cfg.rope_theta)
    v = v.reshape(B, S, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=2)          # query head i -> kv i // g
    v = jnp.repeat(v, nq // nkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=_HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=_HI).reshape(B, S, nq * hd)
    x = x + jnp.einsum("bse,ed->bsd", o, a["wo"], precision=_HI)

    m = p["mlp"]
    h = _norm(x, p["ffn_norm"], cfg.norm_eps)
    gate = jnp.einsum("bsd,df->bsf", h, m["w_gate"], precision=_HI)
    up = jnp.einsum("bsd,df->bsf", h, m["w_up"], precision=_HI)
    act = jax.nn.gelu(gate, approximate=True) if cfg.mlp_act == "geglu" \
        else gate * jax.nn.sigmoid(gate)
    return x + jnp.einsum("bsf,fd->bsd", act * up, m["w_down"], precision=_HI)


def reference_logits(cfg: ArchConfig, params, tokens, at):
    """Float32 logits of ``tokens`` ``(B, S)`` at positions ``at``
    ``(B, K)`` (causal: right padding beyond a row's positions of
    interest does not change them).  Returns ``(B, K, vocab_size)``."""
    _check_supported(cfg)
    B, S = tokens.shape
    emb = params["embed"]
    x = emb["table"][tokens].astype(_F32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    (stack,) = params["stack"]               # one pattern position: dense

    def body(x, layer):
        layer = jax.tree.map(lambda w: w.astype(_F32), layer)
        return _layer(cfg, x, layer, positions), None

    x, _ = jax.lax.scan(body, x, stack)
    x = jnp.take_along_axis(x, at[:, :, None], axis=1)          # (B, K, d)
    x = _norm(x, params["final_norm"].astype(_F32), cfg.norm_eps)
    head = emb["lm_head"] if "lm_head" in emb else emb["table"].T
    logits = jnp.einsum("bkd,dv->bkv", x, head.astype(_F32), precision=_HI)
    return logits[..., :cfg.vocab_size]
