"""Plain float32 reference forwards: dense attention archs and Jamba-style
Mamba + attention hybrids.

Written independently of ``layers``/``blocks``: one ``lax.scan`` over the
stacked layer params, each layer upcast to float32 inside the scan body
(so only one layer's float32 copy is live at a time) and every
contraction at ``Precision.HIGHEST``.  It reads the parameter tree the
model builds and nothing else of the model code, so agreement between
the two is evidence that the model — and the serving engines built on
it — compute the published architecture.

Scope of :func:`reference_logits`: dense decoder stacks of pre-norm GQA
attention (optional QKV bias, rotary embeddings on split halves) and a
gated MLP.  MoE, SSM, fused projections, padded heads and modality
frontends are rejected.

Scope of :func:`hybrid_reference_logits`: Jamba (``model_type`` "jamba",
dense MLPs): Mamba-1 mixers and attention layers where ``layer % period
== offset``, each followed by a gated MLP, all pre-norm.  The Mamba
recurrence is written step by step (one ``lax.scan`` step per token,
from a zero state), with no cache, chunking or conv tail.  Departures
from the published description (Lieber et al., arXiv:2403.19887, and the
Jamba modeling code): everything is computed in float32 where the model
runs bfloat16 with a float32 scan; layers are looped in Python over the
model's stacked parameter tree; nothing else.
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


def _attention(cfg: ArchConfig, x, a, positions):
    """Causal GQA attention of the normed input ``x`` (no residual)."""
    B, S, _ = x.shape
    hd, nq, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    q = jnp.einsum("bsd,de->bse", x, a["wq"], precision=_HI)
    k = jnp.einsum("bsd,de->bse", x, a["wk"], precision=_HI)
    v = jnp.einsum("bsd,de->bse", x, a["wv"], precision=_HI)
    if cfg.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = q.reshape(B, S, nq, hd), k.reshape(B, S, nkv, hd)
    if cfg.rope:
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    v = v.reshape(B, S, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=2)          # query head i -> kv i // g
    v = jnp.repeat(v, nq // nkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=_HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=_HI).reshape(B, S, nq * hd)
    return jnp.einsum("bse,ed->bsd", o, a["wo"], precision=_HI)


def _mlp(cfg: ArchConfig, x, m):
    """Gated MLP of the normed input ``x`` (no residual)."""
    gate = jnp.einsum("bsd,df->bsf", x, m["w_gate"], precision=_HI)
    up = jnp.einsum("bsd,df->bsf", x, m["w_up"], precision=_HI)
    act = jax.nn.gelu(gate, approximate=True) if cfg.mlp_act == "geglu" \
        else gate * jax.nn.sigmoid(gate)
    return jnp.einsum("bsf,fd->bsd", act * up, m["w_down"], precision=_HI)


def _layer(cfg: ArchConfig, x, p, positions):
    x = x + _attention(cfg, _norm(x, p["mixer_norm"], cfg.norm_eps),
                       p["attn"], positions)
    return x + _mlp(cfg, _norm(x, p["ffn_norm"], cfg.norm_eps), p["mlp"])


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


# ------------------------------------------------------- Jamba (hybrid)
def _mamba(cfg: ArchConfig, x, p):
    """Mamba-1 mixer of the normed input ``x`` (B, S, d), step by step.

    in_proj -> (u, z); depthwise causal conv (K taps, bias) and SiLU on
    u; x_proj -> (dt, B, C), each RMS-normed when ``ssm_inner_norm``;
    dt = softplus(dt_proj(dt) + bias); per token t and channel:
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t u_t, y_t = C_t . h_t + D u_t,
    with A = -exp(A_log) and h_{-1} = 0; out_proj(y * SiLU(z))."""
    B, S, d = x.shape
    di, N, K = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    r = -(-d // 16)
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"], precision=_HI)
    u, z = xz[..., :di], xz[..., di:]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    u = sum(up[:, k:k + S] * p["conv_w"][k] for k in range(K)) + p["conv_b"]
    u = u * jax.nn.sigmoid(u)
    proj = jnp.einsum("bse,ef->bsf", u, p["x_proj"], precision=_HI)
    dt, Bm, Cm = proj[..., :r], proj[..., r:r + N], proj[..., r + N:]
    if cfg.ssm_inner_norm:
        dt = _norm(dt, p["dt_norm"], cfg.norm_eps)
        Bm = _norm(Bm, p["b_norm"], cfg.norm_eps)
        Cm = _norm(Cm, p["c_norm"], cfg.norm_eps)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,re->bse", dt, p["dt_proj"], precision=_HI)
        + p["dt_bias"])
    A = -jnp.exp(p["A_log"])                                  # (di, N)

    def step(h, t):
        dt_t, b_t, c_t, u_t = t             # (B, di) (B, N) (B, N) (B, di)
        h = jnp.exp(dt_t[..., None] * A) * h \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t, precision=_HI)

    seq = tuple(a.swapaxes(0, 1) for a in (dt, Bm, Cm, u))
    _, y = jax.lax.scan(step, jnp.zeros((B, di, N), _F32), seq)
    y = y.swapaxes(0, 1) + u * p["D"]
    return jnp.einsum("bse,ed->bsd", y * z * jax.nn.sigmoid(z),
                      p["out_proj"], precision=_HI)


def hybrid_reference_logits(cfg: ArchConfig, params, tokens):
    """Float32 logits ``(B, S, vocab_size)`` at every position of
    ``tokens`` ``(B, S)`` for a Jamba-style hybrid with dense MLPs (see
    the module docstring)."""
    if cfg.n_experts or not cfg.ssm_state or cfg.frontend \
            or cfg.fused_proj or cfg.padded_heads != cfg.n_heads:
        raise ValueError(f"hybrid reference covers Mamba + attention "
                         f"stacks with dense MLPs only, not {cfg.name}")
    with jax.default_matmul_precision("highest"):
        B, S = tokens.shape
        emb = params["embed"]
        x = emb["table"][tokens].astype(_F32)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        stack = params["stack"]
        for i in range(cfg.n_layers):
            p = jax.tree.map(lambda w: w[i // len(stack)].astype(_F32),
                             stack[i % len(stack)])
            h = _norm(x, p["mixer_norm"], cfg.norm_eps)
            if cfg.is_attn_layer(i):
                x = x + _attention(cfg, h, p["attn"], positions)
            else:
                x = x + _mamba(cfg, h, p["mamba"])
            x = x + _mlp(cfg, _norm(x, p["ffn_norm"], cfg.norm_eps),
                         p["mlp"])
        x = _norm(x, params["final_norm"].astype(_F32), cfg.norm_eps)
        head = emb["lm_head"] if "lm_head" in emb else emb["table"].T
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(_F32),
                            precision=_HI)
    return logits[..., :cfg.vocab_size]
