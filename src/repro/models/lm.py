"""Top-level language model: embedding/frontend + block stack + LM head.

One class covers all assigned families; the modality frontends (VLM patch
embeddings, audio frame embeddings) are stubs per the assignment — the
backbone consumes precomputed embeddings provided in the batch.

Batch contracts (all leaves jnp arrays):
  * LM families:  {"tokens": (B, S) i32, "targets": (B, S) i32}
  * vlm:   {"tokens": (B, S_text), "image_embeds": (B, S_img, F),
            "targets": (B, S_text)}
  * audio: {"frame_embeds": (B, S, F), "targets": (B, S, K) i32}
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import blocks, layers
from .config import ArchConfig



def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


@dataclass(frozen=True)
class LanguageModel:
    cfg: ArchConfig
    use_kernel: bool = False
    moe_impl: str = "scatter"
    #: optional PartitionSpec for the (B, S, d) residual stream; pinned at
    #: every block boundary (see blocks._pin_act)
    act_pspec: object = None

    # ------------------------------------------------------------------ init
    def init(self, key) -> dict:
        cfg = self.cfg
        k_emb, k_stack, k_front = jax.random.split(key, 3)
        params = {
            "embed": layers.init_embedding(cfg, k_emb),
            "stack": blocks.init_stack(cfg, k_stack),
            "final_norm": jnp.ones((cfg.d_model,), _dtype(cfg)),
        }
        if cfg.frontend == "vision":
            params["mm_proj"] = jax.random.normal(
                k_front, (cfg.frontend_dim, cfg.d_model), _dtype(cfg)) \
                * (1.0 / math.sqrt(cfg.frontend_dim))
        elif cfg.frontend == "audio":
            params["frame_proj"] = jax.random.normal(
                k_front, (cfg.frontend_dim, cfg.d_model), _dtype(cfg)) \
                * (1.0 / math.sqrt(cfg.frontend_dim))
            params["lm_heads"] = jax.random.normal(
                jax.random.fold_in(k_front, 1),
                (cfg.d_model, cfg.n_codebooks * cfg.vocab_size), _dtype(cfg)) \
                / math.sqrt(cfg.d_model)
        return params

    # ------------------------------------------------------------- embedding
    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        if cfg.frontend == "vision":
            img = batch["image_embeds"].astype(_dtype(cfg)) @ params["mm_proj"]
            txt = layers.embed(params["embed"], batch["tokens"])
            return jnp.concatenate([img, txt], axis=1)
        if cfg.frontend == "audio":
            return batch["frame_embeds"].astype(_dtype(cfg)) \
                @ params["frame_proj"]
        return layers.embed(params["embed"], batch["tokens"])

    def _head(self, params, x):
        cfg = self.cfg
        x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        if cfg.frontend == "audio":
            logits = x @ params["lm_heads"]
            return logits.reshape(*x.shape[:-1], cfg.n_codebooks,
                                  cfg.vocab_size)
        return layers.unembed(params["embed"], x,
                              vocab_size=cfg.vocab_size
                              if cfg.vocab_pad else None)

    # --------------------------------------------------------------- forward
    def forward(self, params, batch):
        """Training-shape forward.  Returns (logits, aux_loss)."""
        x = self._embed_inputs(params, batch)
        x, aux = blocks.stack_apply(
            params["stack"], x, self.cfg, use_kernel=self.use_kernel,
            moe_impl=self.moe_impl, act_pspec=self.act_pspec)
        if self.cfg.frontend == "vision":
            x = x[:, self.cfg.img_seq:]       # logits only over text positions
        return self._head(params, x), aux

    def loss(self, params, batch):
        """Mean next-token cross-entropy (+0.01 * MoE aux loss)."""
        logits, aux = self.forward(params, batch)
        targets = batch["targets"]
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(
            logits.astype(jnp.float32), targets[..., None], axis=-1)[..., 0]
        ce = jnp.mean(lse - gold)
        return ce + 0.01 * aux

    # --------------------------------------------------------------- serving
    def prefill(self, params, batch, max_len: int):
        """Process the prompt; returns (last-position logits, caches)."""
        x = self._embed_inputs(params, batch)
        x, caches = blocks.stack_prefill(
            params["stack"], x, self.cfg, max_len, moe_impl=self.moe_impl,
            act_pspec=self.act_pspec)
        return self._head(params, x[:, -1:]), caches

    def decode_step(self, params, caches, batch, pos, n_valid=None):
        """New tokens from the caches.  ``batch`` carries the inputs at
        positions ``pos ..`` ({"tokens": (B, S)} or {"frame_embeds": (B,
        S, F)}); ``pos`` is the scalar write index of the first.  With
        ``n_valid`` (may be traced) only the first ``n_valid`` tokens are
        real: mamba states stop at the last of them, and only its logits
        ``(B, 1, ...)`` are returned."""
        x = self._embed_inputs(params, batch)
        x, caches = blocks.stack_decode(
            params["stack"], caches, x, self.cfg, pos,
            moe_impl=self.moe_impl, act_pspec=self.act_pspec,
            n_valid=n_valid)
        if n_valid is not None:
            x = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        return self._head(params, x), caches

    def decode_step_paged(self, params, pools, dense, batch, pos, tables):
        """One new token per slot with attention KV in a paged pool
        (``blocks.stack_decode_paged``).  ``batch`` carries ``{"tokens":
        (B, 1)}``; ``pos`` ``(B,)`` each slot's write index; ``tables``
        ``(B, max_blocks)`` its pool block ids.  Returns ``(logits (B, 1,
        V), pools, dense)``."""
        x = self._embed_inputs(params, batch)
        x, pools, dense = blocks.stack_decode_paged(
            params["stack"], pools, dense, x, self.cfg, pos, tables,
            moe_impl=self.moe_impl, act_pspec=self.act_pspec)
        return self._head(params, x), pools, dense

    def init_caches(self, batch_size: int, max_len: int):
        return blocks.init_caches(self.cfg, batch_size, max_len)

    # ------------------------------------------------------------- counting
    def param_count(self, params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    def active_param_count(self, params) -> int:
        """Parameters touched per token (MoE counts top-k of E experts)."""
        cfg = self.cfg
        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
            if any(k in ("w_gate", "w_up", "w_down") for k in keys) \
                    and cfg.n_experts and leaf.ndim == 4:
                total += (leaf.size // cfg.n_experts) * cfg.experts_per_token
            else:
                total += leaf.size
        return total
