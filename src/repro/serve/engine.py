"""Batched serving engine: one jitted prefill + one jitted decode step.

The decode step is the unit the ``decode_*`` / ``long_*`` dry-run cells
lower: one new token against a full-length cache.  Generation here drives
that step in a host loop with greedy/temperature sampling; requests are
batched (static batch; continuous batching is ``serve/paged.py``'s
``PagedContinuousEngine``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..models.lm import LanguageModel


def sample_logits(logits, key, temperature: float = 0.0):
    """logits: (B, 1, V) (or (B, 1, K, V) for audio codebooks)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    scaled = logits.astype(jnp.float32) / temperature
    flat = scaled.reshape(-1, scaled.shape[-1])
    draws = jax.random.categorical(key, flat)
    return draws.reshape(scaled.shape[:-1])


@dataclass
class ServeEngine:
    model: LanguageModel
    params: dict
    max_len: int
    temperature: float = 0.0

    def __post_init__(self):
        self._prefill = jax.jit(
            functools.partial(self.model.prefill, max_len=self.max_len))
        self._decode = jax.jit(self.model.decode_step)
        self._sample = jax.jit(
            functools.partial(sample_logits, temperature=self.temperature))

    def generate(self, tokens, n_new: int, seed: int = 0,
                 eos_id: int | None = None):
        """tokens: (B, S) prompt -> (B, n_new) generated continuation.

        ``eos_id`` (token LMs only): once a sequence samples the eos token
        it stops contributing sampled tokens — every later position is
        padded with ``eos_id`` (the eos itself is kept), and decoding stops
        early when ALL sequences have finished.
        """
        cfg = self.model.cfg
        B, S = tokens.shape
        assert S + n_new <= self.max_len
        if n_new == 0:
            # nothing to generate: an empty (B, 0) continuation, not a
            # jnp.concatenate([]) crash — and no wasted prefill.  Always a
            # jax array, like the n_new >= 1 path (the prompt may be numpy)
            return jnp.asarray(tokens)[:, :0]
        logits, caches = self._prefill(self.params, {"tokens": tokens})
        key = jax.random.PRNGKey(seed)
        out = []
        tok = self._sample(logits, key)                    # (B, 1)
        if eos_id is not None and tok.ndim != 2:
            raise ValueError("eos_id= needs a token LM ((B, 1) samples), "
                             f"got sample shape {tok.shape}")
        finished = jnp.zeros((B, 1), bool)
        for i in range(n_new):
            if eos_id is not None:
                tok = jnp.where(finished, eos_id, tok)
                finished = finished | (tok == eos_id)
            out.append(tok)
            if i == n_new - 1:
                break
            if eos_id is not None and bool(finished.all()):
                break                      # every sequence hit eos: pad rest
            logits, caches = self._decode(
                self.params, caches, {"tokens": tok},
                jnp.asarray(S + i, jnp.int32))
            key = jax.random.fold_in(key, i)
            tok = self._sample(logits, key)
        if len(out) < n_new:               # early-stopped: pad with eos
            out.append(jnp.full((B, n_new - len(out)), eos_id,
                                out[0].dtype))
        return jnp.concatenate(out, axis=1)

    def compiled_steps(self, batch_size: int = 1, prompt_len: int = 32
                       ) -> dict:
        """Compile (without executing) this engine's steps for the advisor:
        ``{"prefill@L": compiled, "decode": compiled}`` — the artifacts
        ``repro.core.price(engine_or_steps, grid)`` prices as one batched
        deployment (see ``serve.paged.PagedContinuousEngine.compiled_steps``
        for the continuous engine's decode and chunk steps)."""
        if self.model.cfg.frontend is not None:
            raise ValueError("compiled_steps lowers a {'tokens': (B, L)} "
                             "batch — token LMs only (multimodal batches "
                             "carry frontend embeddings)")
        p_struct = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.params)
        tok = jax.ShapeDtypeStruct((batch_size, prompt_len), jnp.int32)
        caches = jax.eval_shape(
            lambda: self.model.init_caches(batch_size, self.max_len))
        one = jax.ShapeDtypeStruct((batch_size, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        return {
            f"prefill@{prompt_len}": self._prefill.lower(
                p_struct, {"tokens": tok}).compile(),
            "decode": self._decode.lower(
                p_struct, caches, {"tokens": one}, pos).compile(),
        }
