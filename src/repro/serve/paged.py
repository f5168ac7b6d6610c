"""The continuous-batching serving engine: paged KV, chunked prefill.

``ServeEngine`` (``serve/engine.py``) runs a STATIC batch: every request
prefills together, decodes together, and the batch ends when the longest
request does.  A serving deployment instead sees requests arrive over
time with different prompt and output lengths; ``PagedContinuousEngine``
is the one engine that serves them:

  * a fixed set of decode slots fed from a host-side FIFO request queue;
    eos / length retirement frees a slot, and its KV blocks, for the next
    queued request the moment a sequence finishes (``ServeStats`` keeps
    the occupancy telemetry);
  * attention KV in fixed-size **blocks** drawn from one shared pool (the
    PagedAttention idea, Kwon et al.): each slot owns a chain of blocks, a
    **block table** maps the slot's logical block index to its pool block
    id, and KV bytes scale with the sum of ACTUAL sequence lengths rounded
    up to the block size, not ``n_slots * max_len``.  ``BlockPool`` keeps
    the free list and the reservations (block 0 is the null block: never
    allocated, the write target of inactive slots and the read target of
    unallocated logical blocks, both rendered inert by the causal mask);
  * a paged decode step, jitted ONCE with the pool donated: one batched
    pass over the layer scan in which every attention layer writes each
    slot's new K/V row into the pool in place and attends only the slot's
    live blocks through the block table (``kernels/paged_attention``);
  * **chunked prefill admission** (every arch): the prompt streams through
    one compiled ``block_size``-token chunk step, allocating its block
    right before the chunk runs: one compile for the deployment, whatever
    the prompt lengths, and O(block) activation memory per admission;
  * admission backpressure (a request waits in FIFO order while the pool
    lacks blocks) and a clear :class:`PoolExhausted` error for requests
    that could never fit;
  * profiler spans of each scheduler tick (``repro.serve.step``, with the
    slots' recurrent ``state_bytes`` among its stats) and its parts:
    ``repro.serve.admit`` per admission, ``repro.serve.decode`` (host work
    up to the decode dispatch, with the KV blocks it reads,
    ``kv_blocks_read`` / ``kv_blocks_full``), ``repro.serve.sync`` (the
    sampled tokens' wait and copy) and ``repro.serve.emit`` (emit, retire,
    release).  Their stats are host integers only, so tracing adds no
    sync.

Two kinds of state live side by side.  Attention KV is paged; mamba/SSM
recurrent states (conv tail + ``(d_inner, N)`` state) are O(1) per slot
and stay dense, one row per slot.  The chunk step carries both across
chunks: at attention positions it gathers and writes KV blocks, at mamba
positions it reads the slot's row, runs the chunk from it, and writes the
state as of the chunk's last REAL token back (``mamba.mamba_chunk``: the
padded tail of the last chunk never folds into the state).

Token-for-token greedy parity with the static engine, for attention, SSM
and hybrid archs, is pinned in ``tests/test_scheduler.py`` and
``tests/test_paged.py``.  The deployment's two compiled steps are what
``repro.core.price(engine, grid)`` prices in one batched call.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..models import blocks as blocks_lib
from ..models import mamba as mamba_lib
from ..models.lm import LanguageModel
from .engine import sample_logits


@dataclass
class Request:
    """One generation request.  ``arrival`` is the engine step index at
    which the request becomes visible to the scheduler (0 = immediately);
    ``rid`` is assigned by ``submit``."""

    tokens: np.ndarray                 # (S,) prompt token ids
    max_new_tokens: int
    arrival: int = 0
    rid: int = -1


@dataclass
class ServeStats:
    """Occupancy telemetry of everything the engine has run.

    ``prefills_by_bucket`` counts prefill programs dispatched per compiled
    step (keyed like ``compiled_steps()``: ``"prefill_chunk@bs"``), so
    together with ``decode_steps`` it is the observed step mix that
    :meth:`PagedContinuousEngine.step_weights` feeds back into
    ``MultiSweepResult.predicted_speedup(weights=)``.  ``kv_bytes_peak``
    is the peak pool bytes actually allocated, against
    ``kv_bytes_dense``, the ``n_slots * max_len`` equivalent.
    ``state_bytes`` is the recurrent state the slots in use held at the
    last step (the ``repro.serve.step`` span's stat).  ``kv_blocks_read``
    / ``kv_blocks_full`` sum the ``repro.serve.decode`` span's stats of
    the same names: their ratio is the share of a full-width read the
    decodes moved."""

    n_slots: int
    decode_steps: int = 0        # jitted (n_slots, max_len) steps executed
    slot_steps: int = 0          # Σ active slots over those steps
    prefills: int = 0
    generated_tokens: int = 0
    completed: int = 0
    prefills_by_bucket: dict = field(default_factory=dict)
    kv_bytes_peak: int = 0       # peak allocated pool bytes
    kv_bytes_dense: int = 0      # dense-equivalent n_slots * max_len bytes
    state_bytes: int = 0         # recurrent (mamba) state bytes of the
                                 # slots in use at the last step
    kv_blocks_read: int = 0      # Σ KV blocks the decodes read
    kv_blocks_full: int = 0      # Σ n_slots * max_blocks per decode

    @property
    def occupancy(self) -> float:
        """Fraction of slot-steps that did useful work (1.0 = every slot
        active on every decode step)."""
        return self.slot_steps / max(1, self.decode_steps * self.n_slots)

    @property
    def prefill_calls(self) -> int:
        """Prefill chunks dispatched."""
        return sum(self.prefills_by_bucket.values())


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
class PagedContinuousEngine:
    """Continuous batching over a shared block pool (see module docstring).

    ``block_size`` is the per-block token count (also the chunked-prefill
    chunk length); ``pool_blocks`` sizes the shared pool (0 means the
    dense equivalent ``n_slots * ceil(max_len / block_size)``, i.e. no
    admission backpressure).  ``eos_id`` retires a sequence the moment it
    samples that token; ``seed`` keys the sampler.
    """

    model: LanguageModel
    params: dict
    n_slots: int
    max_len: int
    temperature: float = 0.0
    eos_id: int | None = None
    seed: int = 0
    block_size: int = 16
    pool_blocks: int = 0

    def __post_init__(self):
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1: {self.block_size}")
        cfg = self.model.cfg
        if cfg.frontend is not None:
            raise ValueError("PagedContinuousEngine drives token LMs; "
                             "multimodal decode stays on the static "
                             "ServeEngine")
        self._pattern = blocks_lib.layer_pattern(cfg)
        self._has_kv = any(s.mixer == "attn" for s in self._pattern)
        self._nb = blocks_lib.n_blocks(cfg)
        self._max_blocks = _cdiv(self.max_len, self.block_size)
        if not self.pool_blocks:
            self.pool_blocks = self.n_slots * self._max_blocks
        self._sample = jax.jit(
            functools.partial(sample_logits, temperature=self.temperature))
        self._slot_state_bytes = mamba_lib.state_bytes(cfg) * sum(
            spec.mixer == "mamba" for spec in blocks_lib.layer_specs(cfg))
        donate = (1, 2) if any(s.mixer == "mamba" for s in self._pattern) \
            else (1,)                    # dense tree is all-None: no buffers
        self._decode_paged = jax.jit(self._decode_slots_paged,
                                     donate_argnums=donate)
        self._prefill_chunk = jax.jit(self._prefill_chunk_step,
                                      donate_argnums=donate)
        self._pools = self._make_pools()
        self._dense = self._make_dense()
        self._tables = np.zeros((self.n_slots, self._max_blocks),
                                dtype=np.int32)
        self._slot_blocks = [[] for _ in range(self.n_slots)]
        self._pool = BlockPool(self.pool_blocks)
        self._pos = np.zeros(self.n_slots, dtype=np.int32)
        self._tokens = np.zeros((self.n_slots, 1), dtype=np.int32)
        self._slot_req = [None] * self.n_slots      # Request or None
        self._emitted = np.zeros(self.n_slots, dtype=np.int64)
        self._budget = np.zeros(self.n_slots, dtype=np.int64)
        self._queue: list = []
        self._order: list = []
        self._outputs: dict = {}
        self._next_rid = 0
        self.stats = ServeStats(n_slots=self.n_slots)
        #: rid -> {"queued", "visible", "first", "done"}: perf_counter
        #: stamps at submission, when the request became visible to the
        #: scheduler (``run``'s arrival step, or its admission when driven
        #: through ``step``), its first token and its retirement: what a
        #: load driver turns into TTFT and inter-token percentiles
        self.req_times: dict = {}
        self._key = jax.random.PRNGKey(self.seed)

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
        """What one ``(n_slots, max_len)`` row per slot would cost."""
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
    def submit(self, tokens, max_new_tokens: int, arrival: int = 0) -> int:
        """Queue one request; returns its request id.  A request that
        needs more KV blocks than the whole pool holds fails here with
        :class:`PoolExhausted`."""
        toks = np.asarray(tokens, dtype=np.int32).reshape(-1)
        if len(toks) == 0:
            raise ValueError("empty prompt")
        if len(toks) >= self.max_len:
            raise ValueError(f"prompt of {len(toks)} tokens leaves no room "
                             f"to generate (max_len={self.max_len})")
        req = Request(tokens=toks, max_new_tokens=int(max_new_tokens),
                      arrival=int(arrival), rid=self._next_rid)
        if req.max_new_tokens > 0:        # else nothing is ever admitted
            need = self._blocks_needed(req)
            if not self._pool.fits_ever(need):
                raise PoolExhausted(
                    f"request needs {need} KV blocks (prompt {len(toks)} "
                    f"+ budget tokens at block_size={self.block_size}) but "
                    f"the pool only holds {self._pool.n_blocks}; raise "
                    "pool_blocks= or shorten the request")
        self._next_rid += 1
        self._order.append(req.rid)
        now = time.perf_counter()
        if req.max_new_tokens <= 0:       # nothing to generate: done now
            self._outputs[req.rid] = np.zeros(0, dtype=np.int32)
            self.req_times[req.rid] = {"queued": now, "visible": now,
                                       "first": now, "done": now}
            self.stats.completed += 1
        else:
            self.req_times[req.rid] = {"queued": now}
            self._queue.append(req)
        return req.rid

    def _blocks_needed(self, req: Request) -> int:
        S = len(req.tokens)
        budget = min(req.max_new_tokens, self.max_len - S)
        return _cdiv(S + budget, self.block_size)

    def _alloc_block(self, slot: int, rid: int) -> int:
        blk = self._pool.alloc(rid)
        self._slot_blocks[slot].append(blk)
        self._tables[slot, len(self._slot_blocks[slot]) - 1] = blk
        self.stats.kv_bytes_peak = max(self.stats.kv_bytes_peak,
                                       self.kv_bytes_peak)
        self.stats.kv_bytes_dense = self.kv_bytes_dense
        return blk

    def _prefill_into_slot(self, req: Request, slot: int):
        """Reserve the request's worst-case blocks, stream its prompt
        through the chunk step into ``slot`` (one block allocated right
        before each chunk) and return the last real token's logits."""
        bs = self.block_size
        S = len(req.tokens)
        if not self._pool.try_reserve(req.rid, self._blocks_needed(req)):
            raise PoolExhausted(           # step() gates this
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

    def _admit(self, req: Request, slot: int) -> None:
        S = len(req.tokens)
        t = self.req_times[req.rid]
        start = time.perf_counter()
        t.setdefault("visible", start)
        calls = self.stats.prefill_calls
        with jax.profiler.TraceAnnotation(
                "repro.serve.admit", prompt=S,
                waited_us=int((start - t["queued"]) * 1e6)) as span:
            logits = self._prefill_into_slot(req, slot)
            key = jax.random.fold_in(self._key, req.rid)
            tok = int(np.asarray(self._sample(logits, key))[0, 0])
            span.set_metadata(chunks=self.stats.prefill_calls - calls)
        self._slot_req[slot] = req
        self._pos[slot] = S
        self._tokens[slot, 0] = tok
        self._budget[slot] = min(req.max_new_tokens, self.max_len - S)
        self._emitted[slot] = 0
        self._outputs[req.rid] = []
        self.stats.prefills += 1
        t["first"] = time.perf_counter()
        self._emit(slot, tok)

    def _emit(self, slot: int, tok: int) -> None:
        req = self._slot_req[slot]
        self._outputs[req.rid].append(tok)
        self._emitted[slot] += 1
        self.stats.generated_tokens += 1
        done = self._emitted[slot] >= self._budget[slot] \
            or (self.eos_id is not None and tok == self.eos_id)
        if done:
            self._retire(slot)

    def _retire(self, slot: int) -> None:
        """Finish the slot's request and return its blocks to the pool."""
        req = self._slot_req[slot]
        self._outputs[req.rid] = np.asarray(self._outputs[req.rid],
                                            dtype=np.int32)
        self._slot_req[slot] = None
        self._pos[slot] = 0
        self._tokens[slot, 0] = 0
        self.req_times[req.rid]["done"] = time.perf_counter()
        self.stats.completed += 1
        self._pool.release(req.rid, self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        self._tables[slot, :] = 0          # inactive slots target null

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
        """Allocate the next block for any active slot whose write
        position crossed into an unallocated logical block
        (reservation-backed, so this cannot fail mid-flight), then
        dispatch the paged decode over all slots; returns its (device)
        logits."""
        for slot in range(self.n_slots):
            req = self._slot_req[slot]
            if req is not None and self._pos[slot] // self.block_size \
                    >= len(self._slot_blocks[slot]):
                self._alloc_block(slot, req.rid)
        logits, self._pools, self._dense = self._decode_paged(
            self.params, self._pools, self._dense,
            jnp.asarray(self._tables), jnp.asarray(self._tokens),
            jnp.asarray(self._pos))
        return logits

    def step(self, now: int = 0) -> bool:
        """One scheduler tick: admit what fits, then decode every active
        slot once.  Returns True if any work (admission or decode) ran."""
        with jax.profiler.TraceAnnotation("repro.serve.step",
                                          queued=len(self._queue)) as span:
            calls = self.stats.prefill_calls
            admitted = 0
            for slot in range(self.n_slots):
                if self._slot_req[slot] is not None or not self._queue:
                    continue
                if self._queue[0].arrival > now:
                    break                  # FIFO: don't jump future arrivals
                if self._pool.available < self._blocks_needed(self._queue[0]):
                    break                  # FIFO: wait for blocks to free
                self._admit(self._queue.pop(0), slot)
                admitted += 1
            active = [s for s in range(self.n_slots)
                      if self._slot_req[s] is not None]
            self.stats.state_bytes = len(active) * self._slot_state_bytes
            # live: the positions the decode attends over, summed
            span.set_metadata(
                admitted=admitted, chunks=self.stats.prefill_calls - calls,
                active=len(active),
                live=int(self._pos[active].sum()) + len(active),
                state_bytes=self.stats.state_bytes)
            if not active:
                return False
            with jax.profiler.TraceAnnotation(
                    "repro.serve.decode", **self._decode_reads(active)):
                logits = self._decode_active()
            with jax.profiler.TraceAnnotation("repro.serve.sync"):
                # decode keys live in the upper uint32 half; prefill keys
                # (folded by rid) in the lower — disjoint streams from one
                # seed
                key = jax.random.fold_in(
                    self._key, 0x80000000 + self.stats.decode_steps)
                sampled = np.asarray(self._sample(logits, key))[:, 0]
            self.stats.decode_steps += 1
            self.stats.slot_steps += len(active)
            with jax.profiler.TraceAnnotation("repro.serve.emit"):
                for slot in active:
                    self._pos[slot] += 1
                    tok = int(sampled[slot])
                    self._tokens[slot, 0] = tok
                    self._emit(slot, tok)
            return True

    def run(self, requests=None) -> list:
        """Drain the queue (plus ``requests``: ``(tokens, max_new)`` or
        ``(tokens, max_new, arrival)`` tuples); returns one ``(n_i,)``
        token array per request in submission order."""
        for r in requests or ():
            self.submit(*r)
        self._queue.sort(key=lambda r: (r.arrival, r.rid))
        now = 0
        while self._queue or any(r is not None for r in self._slot_req):
            wall = time.perf_counter()
            for r in self._queue:
                if r.arrival > now:
                    break                  # queue is arrival-sorted
                self.req_times[r.rid].setdefault("visible", wall)
            self.step(now)
            now += 1
        out = [self._outputs[rid] for rid in self._order]
        self._order = []
        self._outputs = {}
        return out

    def step_weights(self) -> dict:
        """Observed step mix of everything run so far, keyed like
        ``compiled_steps()``: ``{"decode": n_decode_steps,
        "prefill_chunk@bs": n_chunks}``.  Pass straight to
        ``MultiSweepResult.predicted_speedup(weights=...)`` (or hand the
        engine itself to ``weights=``: ``_weights`` calls this) so the
        advisor prices the deployment under its ACTUAL load instead of
        one-prefill-one-decode uniformity."""
        return {"decode": float(self.stats.decode_steps),
                **{k: float(v)
                   for k, v in self.stats.prefills_by_bucket.items()}}

    # ------------------------------------------------------ advisor bridge
    def compiled_steps(self) -> dict:
        """Every step this deployment runs, compiled without executing:
        the paged decode and the single chunk-prefill step, for every
        arch, keyed ``"decode"`` / ``"prefill_chunk@bs"``.  This is the
        input to ``repro.core.price(engine, grid)``: price ALL the
        deployment's collectives under one scenario grid in one batched
        super-bundle evaluation."""
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
