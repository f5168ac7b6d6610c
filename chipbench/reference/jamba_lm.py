"""Plain float32 reference of a Jamba hybrid (Mamba-1 + attention, dense
MLPs), as the published ``config.json`` and the Jamba modeling code
describe it.

Pre-norm RMSNorm layers; attention (no positional encoding, GQA, causal)
where ``layer % attn_layer_period == attn_layer_offset``, a Mamba-1 mixer
elsewhere; a SwiGLU MLP in every layer; final RMSNorm and the tied head.
The Mamba mixer: in_proj -> (u, z); depthwise causal conv with bias and
SiLU on u; x_proj -> (dt, B, C), each RMS-normed; dt = softplus(dt_proj
dt + bias); per token t, h_t = exp(dt_t A) h_{t-1} + dt_t B_t u_t and
y_t = C_t . h_t + D u_t, from h = 0, written step by step (one
``lax.scan`` step per token, no cache and no chunks); out_proj(y *
SiLU(z)).  Every contraction at ``Precision.HIGHEST``.  It reads only the
parameter tree and the configuration's published keys.

Departures from the published description: everything is float32 (the
model serves bfloat16 with a float32 scan); attention is computed in
blocks of queries so that an 8,704-token score matrix fits; the layers
run one jitted call each, looped in Python.

``low=True`` is the control: every matrix-product operand, weights and
activations alike, is rounded to float8 (e4m3) first, the precision below
the configuration's bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.dense_lm import F32, _mm, _norm, head


def _attention(m, x, a, low, q_block=512):
    B, S, d = x.shape
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // nq
    qb = math.gcd(q_block, S)
    q = _mm("bsd,de->bse", x, a["wq"], low).reshape(B, S, nq, hd)
    k = _mm("bsd,de->bse", x, a["wk"], low).reshape(B, S, nkv, hd)
    v = _mm("bsd,de->bse", x, a["wv"], low).reshape(B, S, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=2)          # query head i -> kv i // g
    v = jnp.repeat(v, nq // nkv, axis=2)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = _mm("bqhd,bkhd->bhqk", qi, k, low) / math.sqrt(hd)
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(S)
        s = jnp.where(causal, s, -jnp.inf)
        return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, low)

    o = jax.lax.map(block, jnp.arange(S // qb))   # (S/qb, B, qb, H, D)
    o = o.swapaxes(0, 1).reshape(B, S, nq * hd)
    return _mm("bse,ed->bsd", o, a["wo"], low)


def _mamba(m, x, p, low):
    B, S, d = x.shape
    di, N, K = m["mamba_expand"] * d, m["mamba_d_state"], m["mamba_d_conv"]
    r, eps = m["mamba_dt_rank"], m["rms_norm_eps"]
    xz = _mm("bsd,de->bse", x, p["in_proj"], low)
    u, z = xz[..., :di], xz[..., di:]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    u = sum(up[:, k:k + S] * p["conv_w"][k] for k in range(K)) + p["conv_b"]
    u = u * jax.nn.sigmoid(u)
    proj = _mm("bse,ef->bsf", u, p["x_proj"], low)
    dt = _norm(proj[..., :r], p["dt_norm"], eps)
    Bm = _norm(proj[..., r:r + N], p["b_norm"], eps)
    Cm = _norm(proj[..., r + N:], p["c_norm"], eps)
    dt = jax.nn.softplus(_mm("bsr,re->bse", dt, p["dt_proj"], low)
                         + p["dt_bias"])
    A = -jnp.exp(p["A_log"])                                  # (di, N)

    def step(h, t):
        dt_t, b_t, c_t, u_t = t             # (B, di) (B, N) (B, N) (B, di)
        h = jnp.exp(dt_t[..., None] * A) * h \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t,
                             precision=jax.lax.Precision.HIGHEST)

    seq = tuple(a.swapaxes(0, 1) for a in (dt, Bm, Cm, u))
    _, y = jax.lax.scan(step, jnp.zeros((B, di, N), F32), seq)
    y = y.swapaxes(0, 1) + u * p["D"]
    return _mm("bse,ed->bsd", y * z * jax.nn.sigmoid(z), p["out_proj"], low)


def _mlp(m, x, mp, low):
    gate = _mm("bsd,df->bsf", x, mp["w_gate"], low)
    up = _mm("bsd,df->bsf", x, mp["w_up"], low)
    return _mm("bsf,fd->bsd", gate * jax.nn.sigmoid(gate) * up,
               mp["w_down"], low)


def _layer(m, attn, low, x, stacked, b):
    """Layer ``b`` of one stacked pattern position, residuals included."""
    p = jax.tree.map(lambda w: w[b].astype(F32), stacked)
    eps = m["rms_norm_eps"]
    h = _norm(x, p["mixer_norm"], eps)
    x = x + (_attention(m, h, p["attn"], low) if attn
             else _mamba(m, h, p["mamba"], low))
    return x + _mlp(m, _norm(x, p["ffn_norm"], eps), p["mlp"], low)


class Reference:
    """The forward pass of configuration group ``m`` (``low``: the float8
    control), one jitted call per layer kind."""

    def __init__(self, m: dict, low: bool = False):
        self.m, self.low = m, low
        self._layer = {kind: jax.jit(
            lambda x, s, b, kind=kind: _layer(m, kind, low, x, s, b))
            for kind in (True, False)}
        self._head = jax.jit(lambda p, x: head(
            m, p, _norm(x, p["final_norm"].astype(F32), m["rms_norm_eps"]),
            low))

    def hidden(self, params, tokens):
        """Hidden states ``(B, S, d)`` before the final norm."""
        m = self.m
        stack = params["stack"]
        x = params["embed"]["table"][tokens].astype(F32)
        for i in range(m["num_hidden_layers"]):
            attn = i % m["attn_layer_period"] == m["attn_layer_offset"]
            x = self._layer[attn](x, stack[i % len(stack)],
                                  i // len(stack))
        return x

    def logits_at(self, params, tokens, at):
        """Logits ``(B, K, vocab)`` at positions ``at`` ``(B, K)``;
        causal, so right padding past a row's positions does not change
        them."""
        x = self.hidden(params, tokens)
        x = jnp.take_along_axis(x, at[:, :, None], axis=1)
        return self._head(params, x)
