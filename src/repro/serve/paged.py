"""Paged (block) KV cache for continuous-batching serving.

The dense ``ContinuousEngine`` allocates one ``(n_slots, max_len)`` cache
row per slot, so a single long request prices every short request at
``max_len`` memory.  This module stores attention KV in fixed-size
**blocks** drawn from one shared pool instead (the PagedAttention idea,
Kwon et al.): each slot owns a chain of blocks, a **block table** maps the
slot's logical block index to its pool block id, and total KV bytes scale
with the sum of ACTUAL sequence lengths rounded up to the block size —
not ``n_slots * max_len``.

  * ``BlockPool`` — host-side free-list + reservation accounting over pool
    block ids (block 0 is the null block: never allocated, the write
    target of inactive slots and the read target of unallocated logical
    blocks, both rendered inert by the causal mask).
  * ``PagedContinuousEngine`` — drop-in ``ContinuousEngine`` with
      - a paged decode step, jitted ONCE with the pool donated: one
        batched pass over the layer scan in which every attention layer
        writes each slot's new K/V row into the pool in place and attends
        only the slot's live blocks through the block table
        (``kernels/paged_attention``) — no per-slot full-width copy;
      - **chunked prefill admission** (every arch): the prompt streams
        through one compiled ``block_size``-token chunk step, allocating
        its block right before the chunk runs — one compile TOTAL instead
        of one per prefill bucket or prompt length, and O(block)
        activation memory per admission;
      - block free / reuse on eos / length retirement, with admission
        backpressure (a request waits in FIFO order while the pool lacks
        blocks) and a clear :class:`PoolExhausted` error for requests
        that could never fit.

Token-for-token greedy parity with the dense engine is pinned in
``tests/test_paged.py``: the kernel attends exactly the positions the
dense step leaves unmasked.

Two kinds of state live side by side.  Attention KV is paged; mamba/SSM
recurrent states (conv tail + ``(d_inner, N)`` state) are O(1) per slot
and stay dense, one row per slot.  The chunk step carries both across
chunks: at attention positions it gathers and writes KV blocks, at mamba
positions it reads the slot's row, runs the chunk from it, and writes the
state as of the chunk's last REAL token back (``mamba.mamba_chunk``: the
padded tail of the last chunk never folds into the state).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..models import blocks as blocks_lib
from ..models import mamba as mamba_lib
from .scheduler import ContinuousEngine, Request


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


class PoolExhausted(RuntimeError):
    """The request needs more KV blocks than the pool can EVER provide."""


class BlockPool:
    """Free-list + reservation accounting over pool block ids ``1..n``.

    ``reserve`` earmarks a request's worst-case block count (prompt +
    generation budget) at admission, so the lazy per-block ``alloc`` calls
    during decode can never fail mid-flight; ``release`` returns a
    retired request's blocks (and any unused reservation) to the pool.
    Block id 0 is the null block and never enters the free list.
    """

    def __init__(self, n_blocks: int):
        if n_blocks < 1:
            raise ValueError(f"pool needs >= 1 block, got {n_blocks}")
        self.n_blocks = int(n_blocks)
        self._free = list(range(self.n_blocks, 0, -1))   # pop() -> 1, 2, ...
        self._reserved: dict = {}                        # rid -> outstanding
        self.peak_in_use = 0

    @property
    def in_use(self) -> int:
        return self.n_blocks - len(self._free)

    @property
    def available(self) -> int:
        """Blocks neither allocated nor promised to an admitted request."""
        return len(self._free) - sum(self._reserved.values())

    def fits_ever(self, n: int) -> bool:
        return n <= self.n_blocks

    def try_reserve(self, rid: int, n: int) -> bool:
        if n > self.available:
            return False
        self._reserved[rid] = self._reserved.get(rid, 0) + n
        return True

    def alloc(self, rid: int) -> int:
        held = self._reserved.get(rid, 0)
        if held < 1:
            raise PoolExhausted(f"request {rid} allocating beyond its "
                                "reservation (engine bug)")
        self._reserved[rid] = held - 1
        blk = self._free.pop()
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return blk

    def release(self, rid: int, block_ids) -> None:
        self._free.extend(block_ids)
        self._reserved.pop(rid, None)


@dataclass
class PagedContinuousEngine(ContinuousEngine):
    """Continuous batching over a shared block pool (see module docstring).

    ``block_size`` is the per-block token count (also the chunked-prefill
    chunk length); ``pool_blocks`` sizes the shared pool (0 means the
    dense equivalent ``n_slots * ceil(max_len / block_size)``, i.e. no
    admission backpressure).  ``prefill_buckets`` is rejected for
    attention archs — the chunk step replaces bucketed prefill entirely.
    """

    block_size: int = 16
    pool_blocks: int = 0

    def __post_init__(self):
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1: {self.block_size}")
        cfg = self.model.cfg
        self._pattern = blocks_lib.layer_pattern(cfg)
        self._has_kv = any(s.mixer == "attn" for s in self._pattern)
        self._nb = blocks_lib.n_blocks(cfg)
        self._max_blocks = _cdiv(self.max_len, self.block_size)
        if not self.pool_blocks:
            self.pool_blocks = self.n_slots * self._max_blocks
        super().__post_init__()
        if self.prefill_buckets:        # SSM archs already rejected in super
            raise ValueError(
                "PagedContinuousEngine prefills in block_size chunks; "
                "prefill_buckets do not apply (drop them)")
        donate = (1, 2) if any(s.mixer == "mamba" for s in self._pattern) \
            else (1,)                    # dense tree is all-None: no buffers
        self._decode_paged = jax.jit(self._decode_slots_paged,
                                     donate_argnums=donate)
        self._prefill_chunk = jax.jit(self._prefill_chunk_step,
                                      donate_argnums=donate)

    # ---------------------------------------------------------- pool state
    def _make_pools(self):
        """KV pools, one per attention pattern position: ``{"k"/"v":
        (n_layer_blocks, pool_blocks + 1, Hkv, block_size, D)}`` (+1 for
        the null block 0); ``None`` elsewhere.  A pool block is one
        contiguous ``(Hkv, block_size, D)`` run whose per-head tile is
        ``(block_size, D)``: what the decode kernel DMAs and tiles."""
        cfg = self.model.cfg
        dt = jnp.dtype(cfg.dtype)
        hd = cfg.resolved_head_dim
        shape = (self._nb, self.pool_blocks + 1, cfg.n_kv_heads,
                 self.block_size, hd)
        return [{"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
                if spec.mixer == "attn" else None for spec in self._pattern]

    def _make_dense(self):
        """Unpaged per-slot state: mamba/SSM recurrent states (O(1) per
        slot — nothing to page); ``None`` at attention/FFN positions."""
        cfg = self.model.cfg
        dense = []
        for spec in self._pattern:
            if spec.mixer == "mamba":
                st = mamba_lib.init_mamba_state(cfg, self.n_slots)
                dense.append(mamba_lib.MambaState(
                    conv=jnp.broadcast_to(st.conv, (self._nb, *st.conv.shape)),
                    ssm=jnp.broadcast_to(st.ssm, (self._nb, *st.ssm.shape))))
            else:
                dense.append(None)
        return dense

    def _init_cache_state(self):
        self._pools = self._make_pools()
        self._dense = self._make_dense()
        self._tables = np.zeros((self.n_slots, self._max_blocks),
                                dtype=np.int32)
        self._slot_blocks = [[] for _ in range(self.n_slots)]
        self._pool = BlockPool(self.pool_blocks)

    # ----------------------------------------------------------- kv bytes
    @property
    def block_bytes(self) -> int:
        """KV bytes of ONE pool block across all attention layers."""
        total = 0
        for pl in self._pools:
            if pl is not None:
                total += sum(int(np.prod(x.shape[2:])) * x.dtype.itemsize
                             * x.shape[0] for x in pl.values())
        return total

    @property
    def kv_bytes_in_use(self) -> int:
        return self._pool.in_use * self.block_bytes

    @property
    def kv_bytes_peak(self) -> int:
        return self._pool.peak_in_use * self.block_bytes

    @property
    def kv_bytes_dense(self) -> int:
        """What the dense engine's ``(n_slots, max_len)`` rows would cost."""
        return self.n_slots * self._max_blocks * self.block_bytes

    # ------------------------------------------------------------- jitted
    def _gather_slot(self, pool, table_s):
        """One slot's cache through its block table row: the pool's
        blocks gathered into the dense layout ``(nb, max_blocks *
        block_size, Hkv, D)`` (unallocated logical blocks read the null
        block — causally masked)."""
        out = {}
        for name, P in pool.items():
            g = P[:, table_s].swapaxes(2, 3)        # (nb, mb, bs, H, D)
            out[name] = g.reshape(g.shape[0], -1, *g.shape[3:])
        return out

    def _decode_slots_paged(self, params, pools, dense, tables, tokens, pos):
        """One decode step for ALL slots against the shared pool, one
        batched pass over the layer scan (``LanguageModel.decode_step_paged``):
        each attention layer writes every slot's new K/V row into the
        (donated) pool in place and attends only the slot's blocks below
        ``pos + 1`` through the block table; mamba layers step the slots'
        dense rows."""
        return self.model.decode_step_paged(params, pools, dense,
                                            {"tokens": tokens}, pos, tables)

    def _prefill_chunk_step(self, params, pools, dense, table_s, slot, tok,
                            pos, last):
        """One ``block_size``-token prompt chunk for ONE slot, any arch:
        run the multi-token decode step at positions ``pos .. pos + bs -
        1`` from the slot's state, and return the logits of the chunk's
        last real token (index ``last`` in the chunk, traced) with the
        updated pools and dense states.  Attention positions gather the
        slot's cache at full padded width and scatter the chunk's K/V
        block back; mamba positions read the slot's dense row (zeros for
        a prompt's first chunk: the row may hold a retired request's
        state) and write back the state as of token ``last``.  Compiled
        ONCE for the whole deployment — no prefill buckets, no
        per-length compiles."""
        bs = self.block_size
        caches_b = []
        for i, spec in enumerate(self._pattern):
            if spec.mixer == "attn":
                with jax.named_scope("kv_gather"):
                    g = self._gather_slot(pools[i], table_s)
                caches_b.append(jax.tree.map(lambda x: x[:, None], g))
            elif spec.mixer == "mamba":
                with jax.named_scope("ssm_state"):
                    caches_b.append(jax.tree.map(
                        lambda x: jnp.where(
                            pos == 0, jnp.zeros((), x.dtype),
                            jax.lax.dynamic_slice_in_dim(x, slot, 1,
                                                         axis=1)),
                        dense[i]))
            else:
                caches_b.append(None)
        logits, new = self.model.decode_step(
            params, caches_b, {"tokens": tok[None]}, pos, n_valid=last + 1)
        blk = table_s[pos // bs]
        new_pools, new_dense = [], []
        for i, spec in enumerate(self._pattern):
            if spec.mixer == "attn":
                with jax.named_scope("kv_scatter"):
                    new_pools.append(jax.tree.map(
                        lambda P, x: P.at[:, blk].set(
                            jax.lax.dynamic_slice_in_dim(
                                x[:, 0], pos, bs, axis=1).swapaxes(1, 2)),
                        pools[i], new[i]))
                new_dense.append(None)
            elif spec.mixer == "mamba":
                with jax.named_scope("ssm_state"):
                    new_dense.append(jax.tree.map(
                        lambda D, x: jax.lax.dynamic_update_slice_in_dim(
                            D, x, slot, axis=1),
                        dense[i], new[i]))
                new_pools.append(None)
            else:
                new_pools.append(None)
                new_dense.append(None)
        return logits, new_pools, new_dense

    # ------------------------------------------------------- host control
    def _blocks_needed(self, req: Request) -> int:
        S = len(req.tokens)
        budget = min(req.max_new_tokens, self.max_len - S)
        return _cdiv(S + budget, self.block_size)

    def _validate_capacity(self, req: Request) -> None:
        if req.max_new_tokens <= 0:
            return                        # nothing is ever admitted
        need = self._blocks_needed(req)
        if not self._pool.fits_ever(need):
            raise PoolExhausted(
                f"request needs {need} KV blocks (prompt {len(req.tokens)} "
                f"+ budget tokens at block_size={self.block_size}) but the "
                f"pool only holds {self._pool.n_blocks}; raise pool_blocks= "
                "or shorten the request")

    def _can_admit(self, req: Request) -> bool:
        return self._pool.available >= self._blocks_needed(req)

    def _alloc_block(self, slot: int, rid: int) -> int:
        blk = self._pool.alloc(rid)
        self._slot_blocks[slot].append(blk)
        self._tables[slot, len(self._slot_blocks[slot]) - 1] = blk
        self.stats.kv_bytes_peak = max(self.stats.kv_bytes_peak,
                                       self.kv_bytes_peak)
        self.stats.kv_bytes_dense = self.kv_bytes_dense
        return blk

    def _prefill_into_slot(self, req: Request, slot: int):
        bs = self.block_size
        S = len(req.tokens)
        if not self._pool.try_reserve(req.rid, self._blocks_needed(req)):
            raise PoolExhausted(           # _can_admit gates this
                f"admitting request {req.rid} without pool room "
                "(engine bug)")
        n_chunks = _cdiv(S, bs)
        logits = None
        for j in range(n_chunks):
            self._alloc_block(slot, req.rid)     # stream: one per chunk
            chunk = np.zeros(bs, dtype=np.int32)
            part = req.tokens[j * bs:(j + 1) * bs]
            chunk[:len(part)] = part
            logits, self._pools, self._dense = self._prefill_chunk(
                self.params, self._pools, self._dense,
                jnp.asarray(self._tables[slot]), np.int32(slot),
                jnp.asarray(chunk), np.int32(j * bs),
                np.int32(len(part) - 1))
        key = f"prefill_chunk@{bs}"
        self.stats.prefills_by_bucket[key] = \
            self.stats.prefills_by_bucket.get(key, 0) + n_chunks
        return logits

    def _grow_blocks(self) -> None:
        """Allocate the next block for any active slot whose write position
        crossed into an unallocated logical block (reservation-backed, so
        this cannot fail mid-flight)."""
        for slot in range(self.n_slots):
            req = self._slot_req[slot]
            if req is None:
                continue
            if self._pos[slot] // self.block_size \
                    >= len(self._slot_blocks[slot]):
                self._alloc_block(slot, req.rid)

    def _decode_reads(self, active) -> dict:
        """The KV blocks this step's attention reads, Σ over the active
        slots of ``ceil((pos + 1) / block_size)``, against a full-width
        read of ``n_slots * max_blocks``; both added to ``ServeStats``."""
        if not self._has_kv:
            return {"kv_blocks_read": 0, "kv_blocks_full": 0}
        read = int((self._pos[active] // self.block_size + 1).sum())
        full = self.n_slots * self._max_blocks
        self.stats.kv_blocks_read += read
        self.stats.kv_blocks_full += full
        return {"kv_blocks_read": read, "kv_blocks_full": full}

    def _decode_active(self):
        self._grow_blocks()
        logits, self._pools, self._dense = self._decode_paged(
            self.params, self._pools, self._dense,
            jnp.asarray(self._tables), jnp.asarray(self._tokens),
            jnp.asarray(self._pos))
        return logits

    def _retire(self, slot: int) -> None:
        req = self._slot_req[slot]
        super()._retire(slot)
        self._pool.release(req.rid, self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        self._tables[slot, :] = 0          # inactive slots target null

    # ------------------------------------------------------ advisor bridge
    def compiled_steps(self, buckets=None) -> dict:
        """Every step this deployment runs, compiled without executing:
        the paged decode and the single chunk-prefill step, for every
        arch (``buckets`` is ignored: there are none)."""
        p_struct = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.params)
        pools, dense, tables, tokens, pos = self._step_structs()
        out = {"decode": self._decode_paged.lower(
            p_struct, pools, dense, tables, tokens, pos).compile()}
        out[f"prefill_chunk@{self.block_size}"] = self._prefill_chunk.lower(
            p_struct, pools, dense, *self._chunk_structs()).compile()
        return out

    def _step_structs(self):
        """Abstract pools, dense states, tables, tokens and positions of
        the decode step."""
        return (jax.eval_shape(self._make_pools),
                jax.eval_shape(self._make_dense),
                jax.ShapeDtypeStruct((self.n_slots, self._max_blocks),
                                     jnp.int32),
                jax.ShapeDtypeStruct((self.n_slots, 1), jnp.int32),
                jax.ShapeDtypeStruct((self.n_slots,), jnp.int32))

    def _chunk_structs(self):
        """Abstract table row, slot, chunk tokens, position and last index
        of the chunk step."""
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        return (jax.ShapeDtypeStruct((self._max_blocks,), jnp.int32), i32,
                jax.ShapeDtypeStruct((self.block_size,), jnp.int32), i32,
                i32)


# --------------------------------------------------------------------------
# IR-checked entry points (repro.analysis.ircheck registrations)
# --------------------------------------------------------------------------

def _ircheck_engine() -> PagedContinuousEngine:
    """Reduced-config paged engine over abstract params (the IR checker
    only traces/lowers; weights are never materialized)."""
    from ..configs import ARCHS
    from ..models import factory
    cfg = ARCHS["qwen2.5-3b"].reduced()
    model = factory.make_model(cfg, moe_impl="dense")
    return PagedContinuousEngine(
        model=model, params=factory.abstract_params(cfg), n_slots=2,
        max_len=16, block_size=8)


def _ircheck_paged_decode_spec():
    from ..analysis.ircheck import EntrySpec
    eng = _ircheck_engine()
    return EntrySpec(name="serve.paged_decode", fn=eng._decode_paged,
                     args=(eng.params, *eng._step_structs()),
                     donate_argnums=(1,))


def _ircheck_paged_prefill_spec():
    from ..analysis.ircheck import EntrySpec
    eng = _ircheck_engine()
    pools, dense = eng._step_structs()[:2]
    return EntrySpec(name="serve.paged_prefill_chunk",
                     fn=eng._prefill_chunk,
                     args=(eng.params, pools, dense, *eng._chunk_structs()),
                     donate_argnums=(1,))


def register_ircheck_entrypoints(register) -> None:
    """Register the paged serve steps with ``repro.analysis.ircheck`` —
    the pool-donating decode and chunk-prefill jits are prime targets for
    the donation-effectiveness and peak-live-bytes passes."""
    register("serve.paged_decode", _ircheck_paged_decode_spec)
    register("serve.paged_prefill_chunk", _ircheck_paged_prefill_spec)
