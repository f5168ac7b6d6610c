"""Paged decode-attention kernel (interpreted on the CPU) against its
oracle and against the full-width masked ``gqa_attention`` the dense
decode computes, for Qwen-like (8 query heads per KV head) and
Jamba-like (one KV head, 20 query heads) groups at two block sizes.

Each case puts six slots in one call: lengths 1, ``bs - 1``, ``bs``,
``bs + 1`` and the full table, plus an inactive slot (table row all null
block 0, length 1).  Table entries past a slot's length point at block 0,
and every pool row outside the rows the slots attend holds NaN: a kernel
that read a row past a length would return NaN.  The kernel computes a
slot in chunks of ``CHUNK_TOKENS``; at these widths a whole table is one
chunk, so the ``many`` cases shrink the chunk to two blocks to run the
prefetch across chunks and a last chunk with a block past the length."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import paged_attention, \
    paged_attention_ref
from repro.kernels.paged_attention.paged_attention import chunk_pages
from repro.models.layers import gqa_attention

D, N_LAYERS, MAX_BLOCKS = 16, 2, 5
KERNEL = importlib.import_module(
    "repro.kernels.paged_attention.paged_attention")


def _case(n_kv, g, bs, seed=0):
    lengths = [1, bs - 1, bs, bs + 1, MAX_BLOCKS * bs, 1]    # last: inactive
    n_slots = len(lengths)
    n_pool = n_slots * MAX_BLOCKS + 1
    rng = np.random.default_rng(seed)
    tables = np.zeros((n_slots, MAX_BLOCKS), np.int32)
    ids = rng.permutation(np.arange(1, n_pool))
    for s, n in enumerate(lengths[:-1]):
        nb = -(-n // bs)
        tables[s, :nb] = ids[s * MAX_BLOCKS:s * MAX_BLOCKS + nb]
    shape = (N_LAYERS, n_pool, n_kv, bs, D)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    q = rng.standard_normal((n_slots, n_kv, g, D)).astype(np.float32)
    # rows the slots attend: positions < length through the table
    live = np.zeros(shape[:2] + (bs,), bool)
    for s, n in enumerate(lengths):
        for t in range(n):
            live[:, tables[s, t // bs], t % bs] = True
    return q, k, v, tables, np.asarray(lengths, np.int32), live


def _dense(q, k, v, tables, lengths, layer):
    """What the dense decode computed: each slot's cache gathered to full
    width, its one query at position ``length - 1``, causally masked."""
    n_slots, n_kv, g, _ = q.shape
    outs = []
    for s in range(n_slots):
        ck = k[layer, tables[s]].transpose(0, 2, 1, 3).reshape(-1, n_kv, D)
        cv = v[layer, tables[s]].transpose(0, 2, 1, 3).reshape(-1, n_kv, D)
        T = ck.shape[0]
        out = gqa_attention(
            jnp.asarray(q[s]).reshape(1, 1, n_kv * g, D),
            jnp.asarray(ck)[None], jnp.asarray(cv)[None], causal=True,
            kv_positions=jnp.arange(T),
            q_positions=jnp.asarray([lengths[s] - 1]))
        outs.append(out.reshape(n_kv, g, D))
    return jnp.stack(outs)


@pytest.fixture
def chunks(request, monkeypatch):
    """``one``: the kernel's own chunk (a whole table); ``many``: two
    8-token blocks a chunk.  Traces are cleared around it: the chunk is
    read when the kernel is traced."""
    jax.clear_caches()
    if request.param == "many":
        monkeypatch.setattr(KERNEL, "CHUNK_TOKENS", 16)
    yield request.param
    jax.clear_caches()


@pytest.mark.parametrize("chunks,bs", [("one", 8), ("one", 16),
                                       ("many", 8)], indirect=["chunks"])
@pytest.mark.parametrize("n_kv,g", [(2, 8), (1, 20)], ids=["qwen", "jamba"])
def test_kernel_matches_ref_and_dense(n_kv, g, bs, chunks):
    q, k, v, tables, lengths, live = _case(n_kv, g, bs)
    assert KERNEL.chunk_pages(bs, MAX_BLOCKS) == \
        (2 if chunks == "many" else MAX_BLOCKS)
    kn = np.where(live[..., None, :, None], k, np.nan)
    vn = np.where(live[..., None, :, None], v, np.nan)
    layer = N_LAYERS - 1
    out = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn), jnp.asarray(tables),
        jnp.asarray(lengths), jnp.int32(layer)))
    assert np.isfinite(out).all()
    ref = np.asarray(paged_attention_ref(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(tables),
        jnp.asarray(lengths), layer))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    dense = np.asarray(_dense(q, k, v, tables, lengths, layer))
    np.testing.assert_allclose(out, dense, rtol=1e-5, atol=1e-5)
    # a length-1 slot attends one row: its output is that row's values
    np.testing.assert_allclose(
        out[0], np.broadcast_to(v[layer, tables[0, 0], :, :1],
                                (n_kv, g, D)), rtol=1e-6)


def test_kernel_reads_the_layer_it_is_given():
    q, k, v, tables, lengths, _ = _case(2, 8, 8, seed=1)
    args = [jnp.asarray(a) for a in (q, k, v, tables, lengths)]
    for layer in range(N_LAYERS):
        out = paged_attention(*args, jnp.int32(layer))
        ref = paged_attention_ref(*args, layer)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bs,max_blocks,pages", [
    (64, 40, 8), (256, 34, 2), (8, 5, 5), (1024, 8, 1)])
def test_chunk_pages_follow_block_size_and_table(bs, max_blocks, pages):
    """About 512 tokens a chunk, at least one block, at most the table."""
    assert chunk_pages(bs, max_blocks) == pages
