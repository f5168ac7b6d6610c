"""Layer-pattern machinery: every assigned architecture is a stack of
``n_layers`` layers, each layer = mixer (attention | mamba | none) + FFN
(dense | MoE | none), all pre-norm residual.

Heterogeneous stacks (jamba: attention every 8th layer, MoE every 2nd) are
handled by finding the smallest repeating *pattern* of layers; the model then
compiles as ``lax.scan`` over ``n_layers / len(pattern)`` homogeneous
super-blocks.  This keeps the HLO (and TPU compile time) independent of depth
— a 95-layer model lowers to one scanned block body.

Parameters are pytrees stacked along a leading ``n_blocks`` axis (one stack
per pattern position); decode caches follow the same stacking.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import layers, mamba, moe
from .config import ArchConfig


@dataclass(frozen=True)
class LayerSpec:
    mixer: str          # "attn" | "mamba" | "none"
    ffn: str            # "dense" | "moe" | "none"


def layer_specs(cfg: ArchConfig) -> tuple:
    """Per-layer (mixer, ffn) kinds for the full stack."""
    out = []
    for l in range(cfg.n_layers):
        if cfg.is_attn_layer(l):
            mixer = "attn"
        elif cfg.ssm_state:
            mixer = "mamba"
        else:
            raise ValueError(f"layer {l} of {cfg.name} has no mixer")
        if cfg.d_ff == 0:
            ffn = "none"
        elif cfg.is_moe_layer(l):
            ffn = "moe"
        else:
            ffn = "dense"
        out.append(LayerSpec(mixer, ffn))
    return tuple(out)


def layer_pattern(cfg: ArchConfig) -> tuple:
    """Smallest repeating prefix of ``layer_specs`` that tiles the stack."""
    specs = layer_specs(cfg)
    n = len(specs)
    for p in range(1, n + 1):
        if n % p == 0 and all(specs[i] == specs[i % p] for i in range(n)):
            return specs[:p]
    return specs


def n_blocks(cfg: ArchConfig) -> int:
    return cfg.n_layers // len(layer_pattern(cfg))


# ------------------------------------------------------------------- params
def _init_one_layer(cfg: ArchConfig, spec: LayerSpec, key) -> dict:
    k_mix, k_ffn = jax.random.split(key)
    dt = jnp.dtype(cfg.dtype)
    p = {}
    if spec.mixer == "attn":
        p["mixer_norm"] = jnp.ones((cfg.d_model,), dt)
        p["attn"] = layers.init_attention(cfg, k_mix)
    elif spec.mixer == "mamba":
        p["mixer_norm"] = jnp.ones((cfg.d_model,), dt)
        p["mamba"] = mamba.init_mamba(cfg, k_mix)
    if spec.ffn == "dense":
        p["ffn_norm"] = jnp.ones((cfg.d_model,), dt)
        p["mlp"] = layers.init_mlp(cfg, k_ffn)
    elif spec.ffn == "moe":
        p["ffn_norm"] = jnp.ones((cfg.d_model,), dt)
        p["moe"] = moe.init_moe(cfg, k_ffn)
    return p


def init_stack(cfg: ArchConfig, key):
    """Returns a list (one entry per pattern position) of pytrees stacked
    along a leading ``n_blocks`` axis."""
    pattern = layer_pattern(cfg)
    nb = n_blocks(cfg)
    stacked = []
    for pos, spec in enumerate(pattern):
        keys = jax.random.split(jax.random.fold_in(key, pos), nb)
        per_block = [_init_one_layer(cfg, spec, k) for k in keys]
        stacked.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_block))
    return stacked


# -------------------------------------------------------------------- apply
def _apply_layer(p, spec: LayerSpec, x, cfg: ArchConfig, positions,
                 use_kernel: bool, moe_impl: str):
    aux = jnp.zeros((), jnp.float32)
    if spec.mixer == "attn":
        h = layers.rms_norm(x, p["mixer_norm"], cfg.norm_eps)
        x = x + layers.attention_block(p["attn"], h, cfg, positions,
                                       use_kernel=use_kernel)
    elif spec.mixer == "mamba":
        h = layers.rms_norm(x, p["mixer_norm"], cfg.norm_eps)
        x = x + mamba.mamba_block(p["mamba"], h, cfg, use_kernel=use_kernel)
    if spec.ffn == "dense":
        h = layers.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        x = x + layers.mlp_block(p["mlp"], h, cfg)
    elif spec.ffn == "moe":
        h = layers.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        y, aux = moe.moe_ffn(p["moe"], h, cfg, impl=moe_impl)
        x = x + y
    return x, aux


def _pin_act(x, act_pspec):
    """Anchor the residual-stream sharding (batch over the data axes).

    Without this, GSPMD on some backends settles on batch-REPLICATED,
    d-model-sharded activations — 16x the memory and an all-gather per
    layer.  Pinning at every block boundary makes the intended layout the
    fixpoint everywhere inside the scan."""
    if act_pspec is None:
        return x
    return jax.lax.with_sharding_constraint(x, act_pspec)


def stack_apply(stacked, x, cfg: ArchConfig, positions=None,
                use_kernel: bool = False, moe_impl: str = "scatter",
                act_pspec=None):
    """Forward through the whole stack.  Returns (x, total_aux_loss)."""
    pattern = layer_pattern(cfg)

    def block_body(carry, block_params):
        x, aux = carry
        x = _pin_act(x, act_pspec)
        for spec, p in zip(pattern, block_params):
            x, a = _apply_layer(p, spec, x, cfg, positions,
                                use_kernel, moe_impl)
            aux = aux + a
        return (_pin_act(x, act_pspec), aux), None

    body = jax.checkpoint(block_body) if cfg.remat else block_body
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), tuple(stacked))
    else:
        aux = jnp.zeros((), jnp.float32)
        for i in range(n_blocks(cfg)):
            block = [jax.tree.map(lambda a: a[i], s) for s in stacked]
            (x, aux), _ = body((x, aux), block)
    return x, aux


# ----------------------------------------------------------- prefill/decode
def init_caches(cfg: ArchConfig, batch: int, max_len: int):
    """Decode caches stacked like the params: one entry per pattern position.

    attention -> {"k": (nb, B, L, Hkv, D), "v": ..., }; mamba -> MambaState
    with a leading nb axis; pure-FFN positions -> None.
    """
    pattern = layer_pattern(cfg)
    nb = n_blocks(cfg)
    dt = jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    caches = []
    for spec in pattern:
        if spec.mixer == "attn":
            shape = (nb, batch, max_len, cfg.n_kv_heads, hd)
            caches.append({"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)})
        elif spec.mixer == "mamba":
            st = mamba.init_mamba_state(cfg, batch)
            caches.append(mamba.MambaState(
                conv=jnp.broadcast_to(st.conv, (nb, *st.conv.shape)),
                ssm=jnp.broadcast_to(st.ssm, (nb, *st.ssm.shape))))
        else:
            caches.append(None)
    return caches


def stack_prefill(stacked, x, cfg: ArchConfig, max_len: int, positions=None,
                  moe_impl: str = "scatter", act_pspec=None):
    """Forward producing decode caches (padded to ``max_len``)."""
    pattern = layer_pattern(cfg)
    S = x.shape[1]

    def block_body(x, block_params):
        x = _pin_act(x, act_pspec)
        new_caches = []
        for spec, p in zip(pattern, block_params):
            if spec.mixer == "attn":
                h = layers.rms_norm(x, p["mixer_norm"], cfg.norm_eps)
                out, k, v = layers.attention_prefill(p["attn"], h, cfg,
                                                     positions)
                x = x + out
                pad = [(0, 0), (0, max_len - S), (0, 0), (0, 0)]
                new_caches.append({"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)})
            elif spec.mixer == "mamba":
                h = layers.rms_norm(x, p["mixer_norm"], cfg.norm_eps)
                out, state = mamba.mamba_chunk(
                    p["mamba"], h, cfg,
                    mamba.init_mamba_state(cfg, x.shape[0]))
                x = x + out
                new_caches.append(state)
            else:
                new_caches.append(None)
            x = _serve_ffn(p, spec, x, cfg, moe_impl)
        return x, tuple(new_caches)

    if cfg.scan_layers:
        x, caches = jax.lax.scan(block_body, x, tuple(stacked))
    else:
        collected = []
        for i in range(n_blocks(cfg)):
            block = [jax.tree.map(lambda a: a[i], s) for s in stacked]
            x, c = block_body(x, block)
            collected.append(c)
        caches = jax.tree.map(lambda *xs: jnp.stack(xs), *collected)
    return x, list(caches)


def stack_decode(stacked, caches, x, cfg: ArchConfig, pos,
                 moe_impl: str = "scatter", act_pspec=None, n_valid=None):
    """A step of ``S`` new tokens through the stack from its caches.
    x: (B, S, d); pos: scalar index of the first; ``n_valid`` (optional,
    may be traced): the chunk's real tokens, the rest padding — mamba
    layers return their state as of the last real token."""
    pattern = layer_pattern(cfg)

    def block_body(x, scanned):
        block_params, block_caches = scanned
        x = _pin_act(x, act_pspec)
        new_caches = []
        for spec, p, c in zip(pattern, block_params, block_caches):
            if spec.mixer == "attn":
                h = layers.rms_norm(x, p["mixer_norm"], cfg.norm_eps)
                out, ck, cv = layers.attention_decode(
                    p["attn"], h, cfg, c["k"], c["v"], pos)
                x = x + out
                new_caches.append({"k": ck, "v": cv})
            elif spec.mixer == "mamba":
                h = layers.rms_norm(x, p["mixer_norm"], cfg.norm_eps)
                out, state = mamba.mamba_chunk(p["mamba"], h, cfg, c,
                                               n_valid)
                x = x + out
                new_caches.append(state)
            else:
                new_caches.append(None)
            x = _serve_ffn(p, spec, x, cfg, moe_impl)
        return x, tuple(new_caches)

    # caches with None entries can't ride through lax.scan xs; substitute
    # empty arrays for the Nones and restore after.
    def strip(c):
        return {"_empty": jnp.zeros((n_blocks(cfg),), jnp.float32)} \
            if c is None else c

    def body(x, scanned):
        params, caches_in = scanned
        caches_in = [None if (isinstance(c, dict) and "_empty" in c) else c
                     for c in caches_in]
        x, new = block_body(x, (params, caches_in))
        new = tuple({"_empty": jnp.zeros((), jnp.float32)} if c is None else c
                    for c in new)
        return x, new

    if cfg.scan_layers:
        stripped = tuple(strip(c) for c in caches)
        x, new_caches = jax.lax.scan(
            lambda xx, sc: body(xx, sc), x, (tuple(stacked), stripped))
        new_caches = [None if (isinstance(c, dict) and "_empty" in c) else c
                      for c in new_caches]
    else:
        collected = []
        for i in range(n_blocks(cfg)):
            block = [jax.tree.map(lambda a: a[i], s) for s in stacked]
            bc = [None if c is None else jax.tree.map(lambda a: a[i], c)
                  for c in caches]
            x, c = block_body(x, (block, bc))
            collected.append(c)
        new_caches = []
        for pos_i in range(len(pattern)):
            entries = [c[pos_i] for c in collected]
            if entries[0] is None:
                new_caches.append(None)
            else:
                new_caches.append(
                    jax.tree.map(lambda *xs: jnp.stack(xs), *entries))
    return x, list(new_caches)


def stack_decode_paged(stacked, pools, dense, x, cfg: ArchConfig, pos,
                       tables, moe_impl: str = "scatter", act_pspec=None):
    """One decode token per slot through the stack, attention KV paged.

    x: (B, 1, d); pos: (B,) each slot's write index; tables: (B,
    max_blocks) pool block ids.  ``pools`` holds, per pattern position,
    {"k", "v": (n_blocks, n_pool_blocks, Hkv, block_size, D)} at attention
    positions (``None`` elsewhere); they ride the layer scan's carry and
    every layer writes its new rows into them in place
    (:func:`layers.attention_decode_paged`), so no layer's pool is ever
    sliced out or returned whole.  ``dense`` holds the slots' mamba
    states, stacked over the layer blocks, at mamba positions.  Returns
    (x, pools, dense)."""
    pattern = layer_pattern(cfg)

    def block_body(carry, scanned):
        x, pools = carry
        layer, block_params, block_dense = scanned
        x = _pin_act(x, act_pspec)
        pools, new_dense = list(pools), []
        for i, (spec, p) in enumerate(zip(pattern, block_params)):
            new_dense.append(None)
            if spec.mixer == "attn":
                h = layers.rms_norm(x, p["mixer_norm"], cfg.norm_eps)
                out, pools[i] = layers.attention_decode_paged(
                    p["attn"], h, cfg, pools[i], layer, tables, pos)
                x = x + out
            elif spec.mixer == "mamba":
                h = layers.rms_norm(x, p["mixer_norm"], cfg.norm_eps)
                out, new_dense[i] = mamba.mamba_chunk(p["mamba"], h, cfg,
                                                      block_dense[i])
                x = x + out
            x = _serve_ffn(p, spec, x, cfg, moe_impl)
        return (x, tuple(pools)), tuple(new_dense)

    if cfg.scan_layers:
        (x, pools), dense = jax.lax.scan(
            block_body, (x, tuple(pools)),
            (jnp.arange(n_blocks(cfg)), tuple(stacked), tuple(dense)))
        return x, list(pools), list(dense)
    collected = []
    for i in range(n_blocks(cfg)):
        block = [jax.tree.map(lambda a: a[i], s) for s in stacked]
        bd = [None if d is None else jax.tree.map(lambda a: a[i], d)
              for d in dense]
        (x, pools), d = block_body((x, tuple(pools)), (i, block, bd))
        collected.append(d)
    dense = [None if entries[0] is None
             else jax.tree.map(lambda *xs: jnp.stack(xs), *entries)
             for entries in zip(*collected)]
    return x, list(pools), dense


def _serve_ffn(p, spec: LayerSpec, x, cfg: ArchConfig, moe_impl: str):
    """The layer's FFN half on the serving paths (MoE aux loss dropped)."""
    if spec.ffn == "dense":
        h = layers.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        x = x + layers.mlp_block(p["mlp"], h, cfg)
    elif spec.ffn == "moe":
        h = layers.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        y, _ = moe.moe_ffn(p["moe"], h, cfg, impl=moe_impl)
        x = x + y
    return x
