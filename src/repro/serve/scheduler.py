"""Continuous-batching serving engine (slot-based scheduler).

``ServeEngine`` runs a STATIC batch: every request prefills together,
decodes together, and the batch ends when the longest request does.  A
serving deployment instead sees requests arriving over time with different
prompt/output lengths — the orchestration this module owns:

  * one fixed ``(n_slots, max_len)`` decode step, jitted ONCE — per-slot
    position vectors via ``jax.vmap`` of the model's single-sequence decode
    (each slot carries its own write index into its KV/SSM cache row);
  * bucketed prefill-into-slot admission: prompts are right-padded to a
    small set of bucket lengths so admission compiles once per bucket, not
    once per prompt length (causal attention makes the padded positions
    inert, and decode overwrites each stale cache row before attending it);
  * eos / length retirement frees a slot for the next queued request the
    moment a sequence finishes;
  * a host-side FIFO request queue plus occupancy telemetry
    (``ServeStats``);
  * profiler spans of each scheduler tick (``repro.serve.step``, with the
    slots' recurrent ``state_bytes`` among its stats) and its
    parts — ``repro.serve.admit`` per admission, ``repro.serve.decode``
    (host work up to the decode dispatch; the paged engine adds the KV
    blocks it reads, ``kv_blocks_read`` / ``kv_blocks_full``),
    ``repro.serve.sync`` (the sampled tokens' wait and copy) and
    ``repro.serve.emit`` (emit, retire, release).  Their stats are host
    integers only, so tracing adds no sync.

The compiled steps of a deployment (every prefill bucket + the decode
step) are exactly what the batched advisor prices in one call:
``repro.core.price(engine, grid, plan=ExecPlan(...))`` packs all steps'
collectives into one super-bundle evaluation (``CommAdvisor.sweep_serve``
remains as a thin shim).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..models import blocks, mamba
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

    ``prefills_by_bucket`` counts admissions per compiled prefill step
    (keyed like ``compiled_steps()``: ``"prefill@L"`` for the bucketed
    engines, ``"prefill_chunk@bs"`` for the paged chunked path) — together
    with ``decode_steps`` this is the observed step mix that
    :meth:`ContinuousEngine.step_weights` feeds back into
    ``MultiSweepResult.predicted_speedup(weights=)``.  The ``kv_bytes_*``
    fields are populated by the paged engine (0 on the dense engines):
    peak pool bytes actually allocated vs the dense ``n_slots * max_len``
    equivalent.  ``state_bytes`` is the recurrent state the slots in use
    held at the last step (the ``repro.serve.step`` span's stat).
    ``kv_blocks_read`` / ``kv_blocks_full`` sum the ``repro.serve.decode``
    span's stats of the same names (paged engine; 0 on the dense ones):
    their ratio is the share of a full-width read the decodes moved."""

    n_slots: int
    decode_steps: int = 0        # jitted (n_slots, max_len) steps executed
    slot_steps: int = 0          # Σ active slots over those steps
    prefills: int = 0
    generated_tokens: int = 0
    completed: int = 0
    prefills_by_bucket: dict = field(default_factory=dict)
    kv_bytes_peak: int = 0       # paged: peak allocated pool bytes
    kv_bytes_dense: int = 0      # dense-equivalent n_slots * max_len bytes
    state_bytes: int = 0         # recurrent (mamba) state bytes of the
                                 # slots in use at the last step
    kv_blocks_read: int = 0      # paged: Σ KV blocks the decodes read
    kv_blocks_full: int = 0      # paged: Σ n_slots * max_blocks per decode

    @property
    def occupancy(self) -> float:
        """Fraction of slot-steps that did useful work (1.0 = every slot
        active on every decode step)."""
        return self.slot_steps / max(1, self.decode_steps * self.n_slots)

    @property
    def prefill_calls(self) -> int:
        """Prefill programs dispatched (chunks on the paged path)."""
        return sum(self.prefills_by_bucket.values())


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


@dataclass
class ContinuousEngine:
    """Slot-based continuous batching over one jitted decode step.

    ``prefill_buckets`` lists the admission prompt lengths that get their
    own compiled prefill; empty means one power-of-two bucket per distinct
    prompt-length class (compiled lazily).  Padding is an attention-only
    trick — archs with SSM layers admit at the exact prompt length (and
    reject explicit buckets).  ``eos_id`` retires a sequence the moment it
    samples that token.
    """

    model: LanguageModel
    params: dict
    n_slots: int
    max_len: int
    temperature: float = 0.0
    eos_id: int | None = None
    prefill_buckets: tuple = ()
    seed: int = 0

    def __post_init__(self):
        cfg = self.model.cfg
        if cfg.frontend is not None:
            raise ValueError("ContinuousEngine drives token LMs; multimodal "
                             "decode stays on the static ServeEngine")
        # Right-padded bucket prefill is only inert under causal ATTENTION.
        # A mamba/SSM layer folds every position — padding included — into
        # its recurrent state and conv tail, so SSM archs admit at the
        # exact prompt length instead (one compile per distinct length).
        self._exact_prefill = bool(cfg.ssm_state)
        if self._exact_prefill and self.prefill_buckets:
            raise ValueError(
                f"{cfg.name} has SSM layers: bucketed (padded) prefill "
                "would corrupt the recurrent state; omit prefill_buckets "
                "(prompts admit at their exact length)")
        self.prefill_buckets = tuple(sorted(self.prefill_buckets))
        if any(b > self.max_len for b in self.prefill_buckets):
            raise ValueError(f"prefill bucket exceeds max_len="
                             f"{self.max_len}: {self.prefill_buckets}")
        self._prefill = jax.jit(
            functools.partial(self.model.prefill, max_len=self.max_len))
        self._decode = jax.jit(self._decode_slots, donate_argnums=(1,))
        self._write = jax.jit(self._write_slot, donate_argnums=(0,))
        self._sample = jax.jit(
            functools.partial(sample_logits, temperature=self.temperature))
        self._seen_buckets = set(self.prefill_buckets)
        self._slot_state_bytes = mamba.state_bytes(cfg) * sum(
            spec.mixer == "mamba" for spec in blocks.layer_specs(cfg))
        self._reset()

    # ------------------------------------------------------------- jitted
    def _decode_slots(self, params, caches, tokens, pos):
        """One decode step for ALL slots: ``tokens`` ``(n_slots, 1)``,
        ``pos`` ``(n_slots,)`` per-slot write indices.  ``jax.vmap`` of the
        single-sequence decode gives every slot its own cache position —
        the whole step stays one fixed-shape jitted computation."""
        in_ax = jax.tree.map(lambda _: 1, caches)   # batch axis after nb

        def one(caches_slot, tok, p):
            caches_b = jax.tree.map(lambda x: x[:, None], caches_slot)
            logits, new = self.model.decode_step(
                params, caches_b, {"tokens": tok[None]}, p)
            return logits[0], jax.tree.map(lambda x: x[:, 0], new)

        return jax.vmap(one, in_axes=(in_ax, 0, 0),
                        out_axes=(0, in_ax))(caches, tokens, pos)

    def _write_slot(self, caches, new, slot):
        """Admit one prefilled request: overwrite slot ``slot``'s cache row
        (covers the full ``max_len`` axis — no stale state survives)."""
        return jax.tree.map(lambda C, c: C.at[:, slot].set(c[:, 0]),
                            caches, new)

    # ------------------------------------------------------- host control
    def _reset(self):
        self._init_cache_state()
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
        #: through ``step``), its first token and its retirement — what the
        #: load-generator report turns into TTFT / completion-latency
        #: percentiles (serve.loadgen)
        self.req_times: dict = {}
        self._key = jax.random.PRNGKey(self.seed)

    def _init_cache_state(self):
        """Allocate the per-slot decode caches (paged engine overrides)."""
        self.caches = self.model.init_caches(self.n_slots, self.max_len)

    def submit(self, tokens, max_new_tokens: int, arrival: int = 0) -> int:
        """Queue one request; returns its request id."""
        toks = np.asarray(tokens, dtype=np.int32).reshape(-1)
        if len(toks) == 0:
            raise ValueError("empty prompt")
        if len(toks) >= self.max_len:
            raise ValueError(f"prompt of {len(toks)} tokens leaves no room "
                             f"to generate (max_len={self.max_len})")
        req = Request(tokens=toks, max_new_tokens=int(max_new_tokens),
                      arrival=int(arrival), rid=self._next_rid)
        self._validate_capacity(req)
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

    def _validate_capacity(self, req: Request) -> None:
        """Reject requests that can NEVER be admitted (paged engine: more
        blocks than the whole pool holds).  Dense slots always fit."""

    def _bucket_for(self, n: int) -> int:
        if self._exact_prefill:
            return n                      # SSM state: no padding allowed
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return min(self.max_len, _next_pow2(n))

    def _prefill_into_slot(self, req: Request, slot: int):
        """Engine-specific admission: compute the prompt's caches, install
        them into ``slot``, return the last real token's logits.  Dense
        path: one bucketed (right-padded) prefill + a full-row overwrite."""
        S = len(req.tokens)
        L = self._bucket_for(S)
        self._seen_buckets.add(L)
        padded = np.zeros((1, L), dtype=np.int32)
        padded[0, :S] = req.tokens
        logits, new = self._prefill(
            self.params, {"tokens": jnp.asarray(padded)},
            last_index=jnp.asarray([S - 1], jnp.int32))
        self.caches = self._write(self.caches, new, np.int32(slot))
        key = f"prefill@{L}"
        self.stats.prefills_by_bucket[key] = \
            self.stats.prefills_by_bucket.get(key, 0) + 1
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
        req = self._slot_req[slot]
        self._outputs[req.rid] = np.asarray(self._outputs[req.rid],
                                            dtype=np.int32)
        self._slot_req[slot] = None
        self._pos[slot] = 0
        self._tokens[slot, 0] = 0
        self.req_times[req.rid]["done"] = time.perf_counter()
        self.stats.completed += 1

    def _can_admit(self, req: Request) -> bool:
        """Admission backpressure hook: the paged engine defers admission
        while the block pool lacks room (blocks free as slots retire)."""
        return True

    def _decode_reads(self, active) -> dict:
        """Stats of the ``repro.serve.decode`` span about the cache the
        decode reads (paged engine overrides; the dense step reads every
        slot's whole row)."""
        return {}

    def _decode_active(self):
        """Dispatch the jitted decode step over all slots; returns its
        (device) logits (paged engine overrides: block-table growth +
        gather/scatter decode)."""
        logits, self.caches = self._decode(
            self.params, self.caches, jnp.asarray(self._tokens),
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
                if not self._can_admit(self._queue[0]):
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
        ``compiled_steps()`` — ``{"decode": n_decode_steps,
        "prefill@L": n_admissions_at_L, ...}``.  Pass straight to
        ``MultiSweepResult.predicted_speedup(weights=...)`` (or hand the
        engine itself to ``weights=`` — ``_weights`` calls this) so the
        advisor prices the deployment under its ACTUAL load instead of
        one-prefill-one-decode uniformity."""
        return {"decode": float(self.stats.decode_steps),
                **{k: float(v)
                   for k, v in self.stats.prefills_by_bucket.items()}}

    # ------------------------------------------------------ advisor bridge
    def compiled_steps(self, buckets=None) -> dict:
        """Compile (without executing) every step this deployment runs —
        one prefill per bucket + the fixed ``(n_slots, max_len)`` decode —
        keyed ``"prefill@L"`` / ``"decode"``.  ``buckets`` defaults to the
        configured/seen prefill buckets (``max_len`` if none yet).  This is
        the input to ``repro.core.price(engine, grid)``: price ALL the
        deployment's collectives under one scenario grid in one batched
        super-bundle evaluation."""
        buckets = tuple(sorted(buckets or self._seen_buckets)) \
            or (self.max_len,)
        p_struct = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.params)
        out = {}
        for L in buckets:
            tok = jax.ShapeDtypeStruct((1, L), jnp.int32)
            idx = jax.ShapeDtypeStruct((1,), jnp.int32)
            out[f"prefill@{L}"] = self._prefill.lower(
                p_struct, {"tokens": tok}, last_index=idx).compile()
        caches = jax.eval_shape(
            lambda: self.model.init_caches(self.n_slots, self.max_len))
        tokens = jax.ShapeDtypeStruct((self.n_slots, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((self.n_slots,), jnp.int32)
        out["decode"] = self._decode.lower(
            p_struct, caches, tokens, pos).compile()
        return out


# --------------------------------------------------------------------------
# IR-checked entry points (repro.analysis.ircheck registrations)
# --------------------------------------------------------------------------

def _ircheck_engine() -> ContinuousEngine:
    """A reduced-config engine whose params are ShapeDtypeStructs — the
    IR checker only traces/lowers, so no weights are ever materialized
    (``__post_init__`` builds the jits and tiny slot caches; ``params``
    is not touched until a call)."""
    from ..configs import ARCHS
    from ..models import factory
    cfg = ARCHS["qwen2.5-3b"].reduced()
    model = factory.make_model(cfg, moe_impl="dense")
    return ContinuousEngine(model=model, params=factory.abstract_params(cfg),
                            n_slots=2, max_len=16, prefill_buckets=(8,))


def _ircheck_decode_spec():
    from ..analysis.ircheck import EntrySpec
    eng = _ircheck_engine()
    caches = jax.eval_shape(
        lambda: eng.model.init_caches(eng.n_slots, eng.max_len))
    tokens = jax.ShapeDtypeStruct((eng.n_slots, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((eng.n_slots,), jnp.int32)
    return EntrySpec(name="serve.decode", fn=eng._decode,
                     args=(eng.params, caches, tokens, pos),
                     donate_argnums=(1,))


def _ircheck_write_spec():
    from ..analysis.ircheck import EntrySpec
    eng = _ircheck_engine()
    caches = jax.eval_shape(
        lambda: eng.model.init_caches(eng.n_slots, eng.max_len))
    new = jax.eval_shape(lambda: eng.model.init_caches(1, eng.max_len))
    slot = jax.ShapeDtypeStruct((), jnp.int32)
    return EntrySpec(name="serve.write", fn=eng._write,
                     args=(caches, new, slot), donate_argnums=(0,))


def _ircheck_prefill_spec():
    from ..analysis.ircheck import EntrySpec
    eng = _ircheck_engine()
    bucket = eng.prefill_buckets[0]
    tok = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
    idx = jax.ShapeDtypeStruct((1,), jnp.int32)
    return EntrySpec(name="serve.prefill", fn=eng._prefill,
                     args=(eng.params, {"tokens": tok}),
                     kwargs={"last_index": idx})


def register_ircheck_entrypoints(register) -> None:
    """Register the serve steps' representative traced configurations
    with ``repro.analysis.ircheck`` — the two donated jits (``_decode``
    donating the caches, ``_write`` donating the slot cache tree) are the
    donation-effectiveness pass's prime targets."""
    register("serve.decode", _ircheck_decode_spec)
    register("serve.write", _ircheck_write_spec)
    register("serve.prefill", _ircheck_prefill_spec)
