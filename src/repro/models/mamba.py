"""Mamba-1 (selective state-space) mixer — falcon-mamba / jamba layers.

Pure-functional JAX, matching the reference formulation:
    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t * x_t
    y_t = C_t . h_t + D * x_t
with input-dependent (selective) dt/B/C, depthwise causal conv front-end and
a SiLU-gated output path.

Training uses a chunked ``lax.scan`` (checkpointed per chunk so the
backward pass stores O(L/chunk) states, not O(L)); the Pallas kernel in
``repro.kernels.mamba_scan`` replaces it when ``use_kernel`` is set.
Serving runs :func:`mamba_chunk`, one multi-token step that starts from a
``MambaState`` (conv tail + recurrent state) and returns the state as of
the chunk's last real token; prefill and single-token decode are its
special cases.

Jamba's mixer (``cfg.ssm_inner_norm``) applies RMSNorm to ``dt``, ``B``
and ``C`` after ``x_proj`` (``dt_layernorm``/``b_layernorm``/
``c_layernorm`` in the Jamba modeling code).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .config import ArchConfig


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def dt_rank(cfg: ArchConfig) -> int:
    return max(1, math.ceil(cfg.d_model / 16))


class MambaState(NamedTuple):
    """Decode-time carry for one mamba layer."""

    conv: jnp.ndarray   # (B, K-1, d_inner) — last K-1 conv inputs
    ssm: jnp.ndarray    # (B, d_inner, N) — recurrent state, f32


def init_mamba(cfg: ArchConfig, key) -> dict:
    d, di, N, K = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    r = dt_rank(cfg)
    keys = jax.random.split(key, 6)
    dt = _dtype(cfg)
    s = 1.0 / math.sqrt(d)
    # S4D-real initialization of A; dt bias such that softplus(bias) spans
    # [1e-3, 1e-1] as in the reference implementation.
    a = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None, :], (di, 1))
    u = jax.random.uniform(keys[5], (di,), jnp.float32)
    dt_init = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))  # inverse softplus
    p = {
        "in_proj": jax.random.normal(keys[0], (d, 2 * di), dt) * s,
        "conv_w": jax.random.normal(keys[1], (K, di), dt) * (1.0 / math.sqrt(K)),
        "conv_b": jnp.zeros((di,), dt),
        "x_proj": jax.random.normal(keys[2], (di, r + 2 * N), dt)
        * (1.0 / math.sqrt(di)),
        "dt_proj": jax.random.normal(keys[3], (r, di), dt) * (r ** -0.5),
        "dt_bias": dt_bias.astype(jnp.float32),
        "A_log": jnp.log(a),                         # (di, N) f32
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": jax.random.normal(keys[4], (di, d), dt)
        * (1.0 / math.sqrt(di) / math.sqrt(cfg.n_layers)),
    }
    if cfg.ssm_inner_norm:
        p["dt_norm"] = jnp.ones((r,), dt)
        p["b_norm"] = jnp.ones((N,), dt)
        p["c_norm"] = jnp.ones((N,), dt)
    return p


def _causal_conv(x, w, b, tail=None):
    """Depthwise causal conv along time.  x: (B, L, di), w: (K, di);
    ``tail`` (B, K-1, di) holds the inputs before ``x`` (zeros if None)."""
    K = w.shape[0]
    if tail is None:
        pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        pad = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    out = jnp.zeros_like(x)
    for k in range(K):       # K is 4: unrolled taps beat a conv op on TPU
        out = out + pad[:, k: k + x.shape[1], :] * w[k]
    return out + b


def _rms(x, scale, eps):
    """RMSNorm in float32 (the Jamba inner norms; x is f32 already)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _ssm_inputs(p, x, cfg: ArchConfig):
    """x: (B, L, di) post-conv activations -> (dt, B_t, C_t) f32."""
    r, N = dt_rank(cfg), cfg.ssm_state
    proj = (x @ p["x_proj"]).astype(jnp.float32)          # (B, L, r + 2N)
    dt_low, Bt, Ct = jnp.split(proj, [r, r + N], axis=-1)
    if cfg.ssm_inner_norm:
        dt_low = _rms(dt_low, p["dt_norm"], cfg.norm_eps)
        Bt = _rms(Bt, p["b_norm"], cfg.norm_eps)
        Ct = _rms(Ct, p["c_norm"], cfg.norm_eps)
    dt = jax.nn.softplus(dt_low @ p["dt_proj"].astype(jnp.float32)
                         + p["dt_bias"])                  # (B, L, di)
    return dt, Bt, Ct


def selective_scan(x, dt, Bt, Ct, A, D, h0=None, chunk: int = 128):
    """The selective-scan recurrence, chunked + checkpointed.

    x/dt: (B, L, di); Bt/Ct: (B, L, N); A: (di, N); D: (di,).
    Returns (y (B, L, di), h_final (B, di, N)).  All state math in f32.
    """
    Bsz, L, di = x.shape
    N = A.shape[-1]
    xf = x.astype(jnp.float32)
    if h0 is None:
        h0 = jnp.zeros((Bsz, di, N), jnp.float32)

    def step(h, inputs):
        xt, dtt, bt, ct = inputs              # (B,di) (B,di) (B,N) (B,N)
        da = jnp.exp(dtt[..., None] * A)                       # (B, di, N)
        h = da * h + (dtt * xt)[..., None] * bt[:, None, :]    # (B, di, N)
        y = jnp.einsum("bdn,bn->bd", h, ct)
        return h, y

    @jax.checkpoint
    def chunk_scan(h, inputs):
        return jax.lax.scan(step, h, inputs)

    n_chunks = max(1, L // chunk)
    if L % chunk:
        n_chunks, chunk = 1, L                 # irregular tail: single chunk
    # time-major chunks: (n_chunks, chunk, B, ...)
    def to_chunks(a):
        return a.swapaxes(0, 1).reshape(n_chunks, chunk, Bsz, *a.shape[2:])
    inputs = (to_chunks(xf), to_chunks(dt), to_chunks(Bt), to_chunks(Ct))

    h, ys = jax.lax.scan(lambda h, i: chunk_scan(h, i), h0, inputs)
    y = ys.reshape(L, Bsz, di).swapaxes(0, 1)
    y = y + xf * D
    return y, h


def mamba_block(p, x, cfg: ArchConfig, use_kernel: bool = False):
    """Full-sequence mixer.  x: (B, L, d) -> (B, L, d)."""
    xz = x @ p["in_proj"]
    xi, z = jnp.split(xz, 2, axis=-1)                     # (B, L, di) each
    xi = jax.nn.silu(_causal_conv(xi, p["conv_w"], p["conv_b"]))
    dt, Bt, Ct = _ssm_inputs(p, xi, cfg)
    A = -jnp.exp(p["A_log"])
    if use_kernel:
        from ..kernels.mamba_scan import ops as ms_ops
        y, _ = ms_ops.mamba_scan(xi.astype(jnp.float32), dt, Bt, Ct, A, p["D"])
    else:
        y, _ = selective_scan(xi, dt, Bt, Ct, A, p["D"])
    y = y.astype(x.dtype) * jax.nn.silu(z)
    return y @ p["out_proj"]


def mask_tail(dt, x, n_valid):
    """Zero ``dt`` and ``x`` at positions ``>= n_valid``: with ``dt = 0``
    the step leaves ``h`` as it was (``exp(0) = 1``, no input term), so a
    padded chunk tail never folds into the recurrent state."""
    real = (jnp.arange(x.shape[1]) < n_valid)[None, :, None]
    return jnp.where(real, dt, 0.0), jnp.where(real, x, 0)


def mamba_chunk(p, x, cfg: ArchConfig, state: MambaState, n_valid=None):
    """Multi-token step from ``state``.  x: (B, L, d) -> (B, L, d) and the
    state as of token ``n_valid - 1`` (``n_valid`` may be traced; None
    means all ``L`` tokens are real).

    The conv tail is prepended to the chunk's conv inputs and the scan
    starts from ``state.ssm``.  Past ``n_valid`` the outputs are
    meaningless and the state ignores them: ``dt`` and ``x`` are zeroed
    there, and the new tail is the last ``K - 1`` REAL conv inputs, taken
    across the chunk boundary when the chunk is shorter than the tail."""
    K, L = cfg.ssm_conv, x.shape[1]
    xz = x @ p["in_proj"]
    xi, z = jnp.split(xz, 2, axis=-1)                     # (B, L, di) each
    window = jnp.concatenate([state.conv.astype(xi.dtype), xi], axis=1)
    xc = jax.nn.silu(_causal_conv(xi, p["conv_w"], p["conv_b"],
                                  tail=state.conv))
    dt, Bt, Ct = _ssm_inputs(p, xc, cfg)
    if n_valid is None:
        n_valid = L
    else:
        dt, xc = mask_tail(dt, xc, n_valid)
    A = -jnp.exp(p["A_log"])
    with jax.named_scope("ssm_scan"):
        y, h = selective_scan(xc, dt, Bt, Ct, A, p["D"], h0=state.ssm)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    tail = jax.lax.dynamic_slice_in_dim(window, n_valid, K - 1, axis=1)
    return y @ p["out_proj"], MambaState(conv=tail, ssm=h)


def state_bytes(cfg: ArchConfig) -> int:
    """Bytes of one slot's ``MambaState`` in one layer: the conv tail in
    the model's dtype and the recurrent state in float32."""
    di = cfg.d_inner
    return (cfg.ssm_conv - 1) * di * _dtype(cfg).itemsize \
        + di * cfg.ssm_state * 4


def init_mamba_state(cfg: ArchConfig, batch: int) -> MambaState:
    return MambaState(
        conv=jnp.zeros((batch, cfg.ssm_conv - 1, cfg.d_inner), _dtype(cfg)),
        ssm=jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32))
