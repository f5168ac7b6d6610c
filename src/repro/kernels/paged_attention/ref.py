"""Pure-jnp oracle for the paged decode-attention kernel."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def paged_attention_ref(q, k_pool, v_pool, tables, lengths, layer):
    """Same contract as ``ops.paged_attention``, f32 math: gathers every
    slot's whole table row from layer ``layer`` of the pools and masks the
    positions at or past its length."""
    n_slots, n_kv, g, d = q.shape
    k = k_pool[layer][tables].astype(jnp.float32)    # (B, mb, Hkv, bs, D)
    v = v_pool[layer][tables].astype(jnp.float32)
    k = k.transpose(0, 2, 1, 3, 4).reshape(n_slots, n_kv, -1, d)
    v = v.transpose(0, 2, 1, 3, 4).reshape(n_slots, n_kv, -1, d)
    s = jnp.einsum("bhgd,bhtd->bhgt", q.astype(jnp.float32), k) \
        / math.sqrt(d)
    live = jnp.arange(k.shape[2])[None, :] < lengths[:, None]   # (B, T)
    s = jnp.where(live[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgt,bhtd->bhgd", p, v)
    return out.astype(q.dtype)
