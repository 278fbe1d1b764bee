"""Continuous-batching serving engine.

The serving analogue of the Cavs batching policy: the *program* (one
jitted ``decode_step`` over the slot pool) is static; the *occupancy*
(which slots hold live requests, each at its own position) is dynamic
data.  Each engine tick:

  1. admit queued requests into free slots (prefill one sequence,
     ``dynamic_update_slice`` it into the pool — the ``scatter``);
  2. run one batched decode step over ALL slots (inactive slots compute
     garbage that is ignored — padding waste, exactly the paper's
     trade-off, bounded by the admission policy);
  3. sample/argmax next tokens, detect EOS/length-stop, retire finished
     slots (the ``gather`` of results).

This mirrors the Var-LSTM experiment (§5.1): variable-length sequences
batched without recompilation.

Three engines live here:

  - :class:`ServeEngine` — transformer-style decode over a KV-cache
    slot pool (prompt lengths bucketed to powers of two so admission
    reuses one compiled prefill per bucket);
  - :class:`VertexServeEngine` — the Cavs-native serving path: decode
    for *vertex-function* sequence cells (LSTM/GRU), where every engine
    tick is ONE batching task ``V_t`` over the slot pool, routed
    through the scheduler's ``fusion_mode``.  Fused, a tick is a single
    megastep launch (gather previous states + gate math + block
    scatter, buffer aliased in place); unfused it is the op-by-op
    gather → apply → scatter oracle.  Slot occupancy, per-slot
    positions and retirement are pure data — the compiled tick program
    never changes (the Cavs property, now on the decode path);
  - :class:`StructureServeEngine` — request/response serving of WHOLE
    structures (trees/DAGs, e.g. a sentiment service scoring parsed
    sentences), routed through the schedule-compilation pipeline
    (``repro.pipeline``): each dequeued batch is fingerprinted, looked
    up in the schedule cache (repeated topologies skip ``pack_batch``
    and the host→device copy), padded to bucket boundaries (one
    compiled megastep program per bucket, not per shape), and executed
    as one fused batched forward.

All three engines share the robustness layer (``serve/robustness.py``):
``submit`` validates at the door and REJECTS (terminal status, never an
exception) on garbage or a full queue; every request carries an
optional ``ttl`` that becomes a hard deadline; every submitted request
reaches exactly one terminal status (``ok``/``timeout``/``rejected``/
``failed``) and lands in ``engine.finished``; ``engine.health()``
reports queue depth, oldest wait, deadline misses, degradations and
quarantines.  The fused engines degrade to the op-by-op oracle on
kernel failure (a :class:`~repro.serve.robustness.CircuitBreaker` pins
the oracle after ``breaker_threshold`` consecutive failures), and
:class:`StructureServeEngine` quarantines poisoned batches by
bisection so one bad request never takes down its co-batched peers.

Spans (``repro.obs.trace``) time host work: ``serve.decode``,
``serve.tick`` and ``serve.score`` time the dispatch of their programs,
and ``serve.wait`` names each point where an engine itself waits on the
device (the decode's sampled tokens read back, a fused tick's or
batch's ``block_until_ready``).  No span adds a wait of its own.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scheduler import execute, readout_roots, resolve_fusion
from repro.core.structure import InputGraph
from repro.core.vertex import VertexIO
from repro.dist.fault import chaos_fire
from repro.kernels import ops as kops
from repro.kernels.level_megastep import as_rows, from_rows
from repro.obs import trace
from repro.obs.registry import get_registry
from repro.pipeline import (BucketPolicy, SchedulePipeline,
                            graph_fingerprint)
from repro.serve.kv_cache import CacheSlots
from repro.serve.robustness import (ACTIVE, CircuitBreaker,
                                    RequestLifecycle, quarantine_bisect,
                                    validate_prompt, validate_sequence,
                                    validate_structure)

Params = Any


class _EngineBase:
    """Lifecycle plumbing shared by the three engines: ``queue`` and
    ``finished`` are views onto the :class:`RequestLifecycle` (so the
    bounded-queue/terminal-status invariants cannot be bypassed), and
    ``health()`` is the lifecycle's counters plus engine extras,
    schedule-cache tier stats (engines that own a cache or pipeline),
    and — when tracing is on — a summary of the most recent spans."""

    lifecycle: RequestLifecycle

    @property
    def queue(self) -> List[Any]:
        return self.lifecycle.queue

    @queue.setter
    def queue(self, reqs: List[Any]) -> None:
        self.lifecycle.queue = list(reqs)

    @property
    def finished(self) -> List[Any]:
        return self.lifecycle.finished

    def health(self) -> Dict[str, Any]:
        h = self.lifecycle.health(**self._health_extra())
        # Cache/persist tier stats: engines route schedules through
        # either their own ScheduleCache (continuous batching) or a
        # SchedulePipeline (structure serving) — surface whichever
        # exists so hits/disk_hits/packs are one health() away.
        tiers = getattr(self, "cache", None)
        if tiers is None:       # not `or`: an empty cache is len()==0-falsy
            tiers = getattr(self, "pipeline", None)
        stats = getattr(tiers, "stats", None)
        if callable(stats):
            h["schedule_cache"] = stats()
        t = trace.get_tracer()
        if t is not None:
            h["recent_spans"] = t.summary(10)
        return h

    def register_into(self, registry=None, *,
                      name: str = "engine") -> str:
        """Register this engine's :meth:`health` as a snapshot provider
        on ``registry`` (default: the global one); returns the actual
        provider name (suffixed on collision).  Weak-ref'd: a collected
        engine drops out of snapshots on its own."""
        reg = registry if registry is not None else get_registry()
        return reg.register_provider(name, self.health)

    def _health_extra(self) -> Dict[str, Any]:
        return {}


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: np.ndarray               # [prompt_len] int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    ttl: Optional[float] = None      # seconds from submit to deadline
    # -- filled by the engine ------------------------------------------
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    status: str = "new"              # lifecycle: serve/robustness.py
    error: Optional[str] = None


class ServeEngine(_EngineBase):
    """Slot-pool continuous batching over a ``TransformerLM``-style model.

    ``model`` must expose ``prefill(params, tokens, frontend=None)`` →
    ``(last_logits, cache)`` and ``decode_step(params, cache, tokens,
    positions)`` → ``(logits, cache)`` plus ``init_cache``.
    """

    def __init__(self, model, params: Params, *, num_slots: int,
                 max_len: int, cross_len: int = 0,
                 greedy: bool = True, rng: Optional[jax.Array] = None,
                 pad_prompts: bool = True,
                 max_queue: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic):
        #: prompt-length bucketing is exact for attention caches (masked
        #: by kv_len) but NOT for SSM states (pads roll into the state);
        #: engines over SSM/hybrid archs must pass ``pad_prompts=False``.
        self.pad_prompts = pad_prompts
        self.model = model
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.greedy = greedy
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        cache = model.init_cache(num_slots, max_len, cross_len=cross_len)
        self.slots = CacheSlots.create(cache, num_slots)
        self.lifecycle = RequestLifecycle(max_queue=max_queue, clock=clock)
        self._last_token = np.zeros(num_slots, np.int32)
        # jit once; shapes never change across ticks (the Cavs property).
        self._decode = jax.jit(model.decode_step)
        self._prefill = jax.jit(model.prefill)
        self.ticks = 0
        self._live_requests: Dict[int, Request] = {}

    # -- ingress ------------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Validate + enqueue; returns False (and routes ``req`` to the
        ``rejected`` terminal) on garbage input or a full queue."""
        err = validate_prompt(req.prompt, self.max_len, req.max_new_tokens)
        return self.lifecycle.submit(req, err)

    # -- one engine tick -------------------------------------------------------
    def step(self) -> int:
        """Admit + decode one token for all active slots.  Returns the
        number of live requests after the tick."""
        self.lifecycle.sweep_deadlines()
        self._retire_expired()
        self._admit()
        if self.slots.num_active == 0:
            return len(self.queue)
        # .copy(): _last_token is mutated in place after this tick, and
        # jnp.asarray of numpy is zero-copy on CPU (aliasing + async
        # dispatch = race).  positions_device() copies likewise.
        tokens = jnp.asarray(self._last_token.copy())[:, None]
        positions = self.slots.positions_device()
        with trace.span("serve.decode", active=int(self.slots.num_active)):
            logits, new_cache = self._decode(self.params, self.slots.cache,
                                             tokens, positions)
        self.slots.cache = new_cache
        next_tok = self._sample(logits)
        self.slots.advance()
        self.ticks += 1

        with trace.span("serve.wait"):
            next_np = np.asarray(next_tok)
        for slot in range(self.num_slots):
            if not self.slots.active[slot]:
                continue
            rid = self.slots.request_of[slot]
            req = self._req_by_id(rid)
            tok = int(next_np[slot])
            req.output.append(tok)
            self._last_token[slot] = tok
            stop = (req.eos_id is not None and tok == req.eos_id) or \
                len(req.output) >= req.max_new_tokens or \
                int(self.slots.positions[slot]) >= self.max_len
            if stop:
                self._live_requests.pop(req.request_id, None)
                self.lifecycle.finish_ok(req)
                self.slots.retire(slot)
            elif self.lifecycle.expired(req):
                # In-flight deadline: retire with whatever decoded so far.
                self._live_requests.pop(req.request_id, None)
                self.lifecycle.finish_timeout(req)
                self.slots.retire(slot)
        return self.slots.num_active + len(self.queue)

    def run(self, max_ticks: int = 10_000) -> List[Request]:
        """Drain the queue; returns finished requests."""
        for _ in range(max_ticks):
            if self.step() == 0 and not self.queue:
                break
        return self.finished

    # -- internals ------------------------------------------------------------
    def _retire_expired(self) -> None:
        """Retire in-flight requests whose deadline passed between ticks
        (partial output stays on the request)."""
        for slot in range(self.num_slots):
            if not self.slots.active[slot]:
                continue
            req = self._req_by_id(self.slots.request_of[slot])
            if self.lifecycle.expired(req):
                self._live_requests.pop(req.request_id, None)
                self.lifecycle.finish_timeout(req)
                self.slots.retire(slot)

    def _admit(self) -> None:
        free = self.slots.free_slots()
        while free and self.queue:
            slot = free.pop(0)
            req = self.queue.pop(0)
            req.status = ACTIVE
            with trace.correlate(request=req.request_id), \
                    trace.span("serve.prefill", slot=slot,
                               prompt_len=len(req.prompt)):
                self._admit_one(slot, req)

    def _admit_one(self, slot: int, req: Request) -> None:
        # Bucket the prompt length to a power of two: one compiled
        # prefill program per bucket, not per length (the
        # recompilation cost Cavs exists to avoid).  The pad is on
        # the *right*; we prefill only the first ``plen - 1`` real
        # tokens' effects by admitting with ``prompt_len = plen - 1``
        # and replaying the last prompt token through the decode
        # step — its fresh K/V overwrites the first pad row, and
        # ``kv_len`` masking hides the rest, so attention is exact.
        plen = len(req.prompt)
        prompt = np.asarray(req.prompt, np.int32)
        bucket = max(8, 1 << (plen - 1).bit_length()) \
            if self.pad_prompts else plen
        padded = np.concatenate(
            [prompt, np.zeros(bucket - plen, np.int32)])
        logits, cache1 = self._prefill(self.params,
                                       jnp.asarray(padded)[None, :])
        if bucket == plen:
            # Exact prompt (pad_prompts=False, required for SSM
            # state exactness): the prefilled cache/state already
            # includes the last token; take the first output token
            # from the prefill logits directly.
            self.slots.admit(slot, req.request_id, cache1,
                             prompt_len=plen)
            tok = int(np.asarray(self._sample(logits[None]
                                              if logits.ndim == 1
                                              else logits))[0])
            req.output.append(tok)
            self._last_token[slot] = tok
        else:
            # Padded prompt: prefill's last position is a pad, so
            # admit at plen-1 and REPLAY the final prompt token
            # through the decode step — its fresh K/V overwrites the
            # first pad row and kv_len masking hides the rest.
            self.slots.admit(slot, req.request_id, cache1,
                             prompt_len=plen - 1)
            self._last_token[slot] = int(prompt[-1])
        self._live_requests[req.request_id] = req

    def _req_by_id(self, rid: int) -> Request:
        return self._live_requests[rid]

    def _sample(self, logits: jax.Array) -> jax.Array:
        if self.greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.rng, sub = jax.random.split(self.rng)
        return jax.random.categorical(sub, logits).astype(jnp.int32)

    def _health_extra(self) -> Dict[str, Any]:
        return {"active_slots": int(self.slots.num_active),
                "num_slots": self.num_slots, "ticks": self.ticks}


# ---------------------------------------------------------------------------
# Vertex-function serving (the Cavs decode path, fusion_mode-aware)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class VertexRequest:
    """One streaming sequence for :class:`VertexServeEngine`.

    ``inputs``: ``[L, X_raw]`` external rows (tokens' embeddings,
    features, ...), consumed one per engine tick.  The engine fills
    ``final_state`` (``[S]``) when the sequence is exhausted.
    """

    request_id: int
    inputs: np.ndarray
    ttl: Optional[float] = None      # seconds from submit to deadline
    # -- filled by the engine ------------------------------------------
    final_state: Optional[np.ndarray] = None
    done: bool = False
    status: str = "new"              # lifecycle: serve/robustness.py
    error: Optional[str] = None

    @property
    def length(self) -> int:
        return int(self.inputs.shape[0])


class VertexServeEngine(_EngineBase):
    """Continuous batching for arity-1 vertex functions (LSTM/GRU).

    Each tick advances every active slot by one vertex: slot ``m``
    gathers its previous state, pulls its next external row, and
    scatters the new state — i.e. one batching task ``V_t`` of width
    ``num_slots``.  The state pool is a ping-pong buffer
    ``[2*num_slots + 1, S]`` (last row = zero sentinel): tick parity
    ``p`` reads block ``p`` and writes block ``1-p``, so reads and
    writes never overlap — the same non-overlap invariant that makes
    the training megastep's in-place alias sound.  Fresh slots point
    their gather at the sentinel (zero initial state) via the child
    mask, so admission/retirement is pure data.

    ``fusion_mode`` is resolved exactly like the scheduler's
    (:func:`repro.core.scheduler.resolve_fusion`): when the cell
    declares a :class:`~repro.core.vertex.GateSpec`, the tick is ONE
    fused megastep launch; ``"none"`` keeps the op-by-op oracle tick.
    """

    def __init__(self, fn, params: Params, *, num_slots: int,
                 fusion_mode: str = "auto",
                 max_queue: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic,
                 breaker_threshold: int = 3):
        if getattr(fn, "arity", None) != 1:
            raise ValueError(
                f"VertexServeEngine decodes chains (arity-1 cells); "
                f"{type(fn).__name__} has arity {getattr(fn, 'arity', None)}")
        self.fn = fn
        self.params = params
        self.num_slots = num_slots
        self.spec = resolve_fusion(fn, fusion_mode, sched_arity=1)
        S = fn.state_dim
        self._buf = jnp.zeros((2 * num_slots + 1, S), jnp.float32)
        self._parity = 0
        self._pos = np.zeros(num_slots, np.int64)
        self._slot_req: List[Optional[VertexRequest]] = [None] * num_slots
        self.lifecycle = RequestLifecycle(max_queue=max_queue, clock=clock)
        self._breaker = CircuitBreaker(breaker_threshold)
        self.ticks = 0
        self._tick = jax.jit(functools.partial(_vertex_tick, fn, self.spec))
        # The degradation rung: the same tick with spec=None is the
        # op-by-op oracle (gather → apply → scatter, no megastep).
        self._tick_oracle = jax.jit(functools.partial(_vertex_tick, fn,
                                                      None))

    @property
    def fused(self) -> bool:
        """True when ticks run as single megastep launches (False once
        the circuit breaker has pinned the oracle)."""
        return self.spec is not None and not self._breaker.open

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self._slot_req)

    # -- ingress ------------------------------------------------------------
    def submit(self, req: VertexRequest) -> bool:
        """Validate + enqueue; returns False (and routes ``req`` to the
        ``rejected`` terminal) on garbage input or a full queue."""
        err = validate_sequence(req.inputs, self.fn.input_dim)
        return self.lifecycle.submit(req, err)

    # -- one engine tick -----------------------------------------------------
    def step(self) -> int:
        """Admit + advance every active slot one vertex.  Returns live
        requests (active + queued) after the tick."""
        self.lifecycle.sweep_deadlines()
        expired_slots = []
        for m, req in enumerate(self._slot_req):
            if req is not None and self.lifecycle.expired(req):
                self.lifecycle.finish_timeout(req)
                self._slot_req[m] = None
                expired_slots.append(m)
        self._zero_slot_rows(expired_slots)
        for m in range(self.num_slots):
            if self._slot_req[m] is None and self.queue:
                req = self.queue.pop(0)
                req.status = ACTIVE
                self._slot_req[m] = req
                self._pos[m] = 0
        if self.num_active == 0:
            return len(self.queue)

        M = self.num_slots
        base, out_base = self._parity * M, (1 - self._parity) * M
        x_dim = self.fn.input_dim
        child_ids = np.full((M, 1), 2 * M, np.int32)       # sentinel
        child_mask = np.zeros((M, 1), np.float32)
        ext_rows = np.zeros((M, x_dim), np.float32)
        node_mask = np.zeros((M,), np.float32)
        for m, req in enumerate(self._slot_req):
            if req is None:
                continue
            node_mask[m] = 1.0
            ext_rows[m] = req.inputs[self._pos[m]]
            if self._pos[m] > 0:
                child_ids[m, 0] = base + m
                child_mask[m, 0] = 1.0
        args = (self.params, self._buf, jnp.asarray(child_ids),
                jnp.asarray(child_mask), jnp.asarray(ext_rows),
                jnp.asarray(node_mask), jnp.int32(out_base))
        try:
            with trace.span("serve.tick", active=self.num_active,
                            fused=self.fused):
                self._buf = self._run_tick(args)
        except Exception as e:           # noqa: BLE001 — oracle failed too
            # Both rungs of the ladder failed: the whole tick is lost
            # (the buffer was not advanced), so every in-flight request
            # reaches the ``failed`` terminal — queued requests are
            # untouched and will be admitted next tick.
            failed_slots = []
            for m, req in enumerate(self._slot_req):
                if req is not None:
                    self.lifecycle.finish_failed(req, f"tick failed: {e}")
                    self._slot_req[m] = None
                    failed_slots.append(m)
            self._zero_slot_rows(failed_slots)
            return self.num_active + len(self.queue)
        self._parity = 1 - self._parity
        self.ticks += 1

        done_rows = None
        for m, req in enumerate(self._slot_req):
            if req is None:
                continue
            self._pos[m] += 1
            if self._pos[m] >= req.length:
                if done_rows is None:
                    done_rows = np.asarray(self._buf[out_base: out_base + M])
                req.final_state = done_rows[m].copy()
                self.lifecycle.finish_ok(req)
                self._slot_req[m] = None
        return self.num_active + len(self.queue)

    def _zero_slot_rows(self, slots: List[int]) -> None:
        """Re-zero BOTH ping-pong rows of slots freed by a timeout or a
        failed tick.  A fresh admission gathers the zero sentinel at
        position 0, so correctness never reads the stale rows — but a
        dead request's states must not linger in the pool (leak hygiene,
        and the invariant the regression test pins: a freed slot's rows
        are exactly zero before reuse)."""
        if not slots:
            return
        M = self.num_slots
        rows = np.asarray([m for s in slots for m in (s, M + s)], np.int32)
        self._buf = self._buf.at[jnp.asarray(rows)].set(0.0)

    def _run_tick(self, args: Tuple) -> jax.Array:
        """One tick through the degradation ladder: fused megastep
        first; on failure fall back to the op-by-op oracle for THIS tick
        (same math, no fused kernel), and once the breaker trips, pin
        the oracle without re-trying the fused path."""
        if self.fused:
            try:
                chaos_fire("kernel")
                out = self._tick(*args)
                with trace.span("serve.wait"):
                    out.block_until_ready()  # surface async kernel failures
                self._breaker.record_success()
                return out
            except Exception as e:       # noqa: BLE001 — degrade
                self._breaker.record_failure()
                self.lifecycle.degrade(e)
        return self._tick_oracle(*args)

    def run(self, max_ticks: int = 100_000) -> List[VertexRequest]:
        """Drain the queue; returns finished requests."""
        for _ in range(max_ticks):
            if self.step() == 0:
                break
        return self.finished

    def _health_extra(self) -> Dict[str, Any]:
        return {"active_slots": self.num_active, "ticks": self.ticks,
                "breaker_open": self._breaker.open,
                "breaker_trips": self._breaker.trips}


# ---------------------------------------------------------------------------
# Whole-structure serving (the schedule pipeline on the request path)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StructureRequest:
    """One structure to score: the topology ``G`` plus its per-node
    external inputs ``[num_nodes, X_raw]``.  The engine fills
    ``root_state`` (``[S]``) — the batched readout of the root vertex."""

    request_id: int
    graph: InputGraph
    inputs: np.ndarray
    ttl: Optional[float] = None      # seconds from submit to deadline
    # -- filled by the engine ------------------------------------------
    root_state: Optional[np.ndarray] = None
    done: bool = False
    status: str = "new"              # lifecycle: serve/robustness.py
    error: Optional[str] = None


class StructureServeEngine(_EngineBase):
    """Batch scoring of queued structures through the schedule pipeline.

    Each :meth:`step` dequeues up to ``batch_size`` requests and runs
    ONE batched fused forward over them.  The pipeline makes the host
    path disappear under load: repeated topologies hit the schedule
    cache (no ``pack_batch``, no host→device schedule copy), and the
    bucket policy quantizes padded dims so the jitted forward compiles
    once per bucket instead of once per shape —
    ``engine.pipeline.stats()`` reports both effects (hit rate and
    compiled-shape count).

    ``compose=True`` (default) additionally COMPOSES each dequeued
    batch instead of slicing the queue FIFO: the batch is anchored on
    the oldest pending request (no starvation) and filled with every
    queued request sharing its topology fingerprint first — the batch
    most likely to be a schedule-cache hit — then topped up FIFO.
    Responses are per-request objects, so reordering is invisible to
    callers beyond latency.
    """

    def __init__(self, fn, params: Params, *, batch_size: int = 16,
                 pipeline: Optional[SchedulePipeline] = None,
                 fusion_mode: str = "auto", compose: bool = True,
                 max_queue: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic,
                 breaker_threshold: int = 3,
                 guard_nonfinite: bool = True):
        self.fn = fn
        self.params = params
        self.batch_size = batch_size
        self.compose = compose
        self.pipeline = pipeline if pipeline is not None else \
            SchedulePipeline(fn.input_dim,
                             bucket_policy=BucketPolicy(mode="pow2"),
                             # forward-only consumer: pack without the
                             # backward's sorted-run arrays (~4x smaller
                             # cache/persist entries)
                             with_runs=False)
        self.lifecycle = RequestLifecycle(max_queue=max_queue, clock=clock)
        self._breaker = CircuitBreaker(breaker_threshold)
        #: a request whose finite inputs still produced a non-finite
        #: root state (model blowup, chaos NaN injection past the door)
        #: fails ALONE — NaNs are block-diagonal in the batched forward,
        #: so attribution is direct, no bisection needed.
        self.guard_nonfinite = guard_nonfinite
        self._fusion = fusion_mode
        self.batches = 0
        self._run = jax.jit(functools.partial(_structure_batch, fn,
                                              fusion_mode))
        self._run_oracle = jax.jit(functools.partial(_structure_batch, fn,
                                                     "none"))

    # -- ingress ------------------------------------------------------------
    def submit(self, req: StructureRequest) -> bool:
        """Validate + enqueue; returns False (and routes ``req`` to the
        ``rejected`` terminal) on a malformed structure, non-finite
        inputs, a full queue, or a double-submitted request object (the
        engine fills requests in place — one object, one lifecycle)."""
        err = validate_structure(req.graph, req.inputs, self.fn.input_dim)
        if err is not None:
            err = f"request {req.request_id}: {err}"
        return self.lifecycle.submit(req, err)

    @property
    def fused(self) -> bool:
        """True while batches attempt the fused forward (False once the
        circuit breaker has pinned the op-by-op oracle)."""
        return self._fusion != "none" and not self._breaker.open

    # -- one engine batch ----------------------------------------------------
    def step(self) -> int:
        """Score one batch of queued requests.  Returns requests still
        queued after the batch."""
        self.lifecycle.sweep_deadlines()
        if not self.queue:
            return 0
        with trace.span("serve.flush"):
            reqs = (self._compose_flush() if self.compose
                    else self.queue[: self.batch_size])
        taken = set(id(r) for r in reqs)   # by identity: requests hold
        self.queue = [r for r in self.queue  # ndarrays, so == is unusable
                      if id(r) not in taken]
        for r in reqs:
            r.status = ACTIVE

        poisoned = [False]

        def run_fn(batch_reqs):
            try:
                return self._run_batch(batch_reqs)
            except Exception:
                poisoned[0] = True
                raise

        def on_fail(req, exc):
            self.lifecycle.finish_failed(
                req, f"batch execution failed: {exc}")

        with trace.span("serve.batch", size=len(reqs)):
            pairs = quarantine_bisect(list(reqs), run_fn, on_fail)
        if poisoned[0]:
            self.lifecycle.quarantines += 1
        self.batches += 1
        for req, root in pairs:
            if self.guard_nonfinite and not np.isfinite(root).all():
                self.lifecycle.finish_failed(req, "non-finite root state")
                continue
            req.root_state = root.copy()
            self.lifecycle.finish_ok(req)
        return len(self.queue)

    def run(self, max_batches: int = 10_000) -> List[StructureRequest]:
        """Drain the queue; returns finished requests."""
        for _ in range(max_batches):
            if self.step() == 0:
                break
        return self.finished

    # -- internals ----------------------------------------------------------
    def _compose_flush(self) -> List[StructureRequest]:
        """The batch to flush: anchored on the OLDEST pending request
        (bounded latency), filled with same-fingerprint peers from
        anywhere in the queue (the composed cache-hit batch), topped up
        FIFO when the group runs short.  Same-fingerprint requests are
        kept in queue order, so a recurring group composes the same
        ordered digest sequence every flush — a schedule-cache hit."""
        anchor_fp = graph_fingerprint(self.queue[0].graph)
        batch = [r for r in self.queue
                 if graph_fingerprint(r.graph) == anchor_fp]
        batch = batch[: self.batch_size]
        if len(batch) < self.batch_size:
            chosen = set(id(r) for r in batch)
            for r in self.queue:
                if len(batch) >= self.batch_size:
                    break
                if id(r) not in chosen:
                    batch.append(r)
        return batch

    def _run_batch(self, reqs: List[StructureRequest]) -> List[np.ndarray]:
        """Pack + score one (sub-)batch; per-request root-state rows.
        Raises on pack or kernel failure — the quarantine bisect above
        narrows the blast radius to the poisoned request."""
        batch = self.pipeline.pack([r.graph for r in reqs],
                                   [np.asarray(r.inputs, np.float32)
                                    for r in reqs])
        with trace.span("serve.score", size=len(reqs), fused=self.fused):
            roots = np.asarray(self._score(batch.dev, batch.ext))
        return [roots[k] for k in range(len(reqs))]

    def _score(self, dev, ext) -> jax.Array:
        """The degradation ladder: fused forward first; on failure fall
        back to the op-by-op oracle for THIS batch, and once the breaker
        trips, pin the oracle without re-trying the fused path."""
        if self.fused:
            try:
                chaos_fire("kernel")
                out = self._run(self.params, dev, ext)
                with trace.span("serve.wait"):
                    out.block_until_ready()  # surface async kernel failures
                self._breaker.record_success()
                return out
            except Exception as e:       # noqa: BLE001 — degrade
                self._breaker.record_failure()
                self.lifecycle.degrade(e)
        return self._run_oracle(self.params, dev, ext)

    def _health_extra(self) -> Dict[str, Any]:
        return {"batches": self.batches,
                "breaker_open": self._breaker.open,
                "breaker_trips": self._breaker.trips}


def _structure_batch(fn, fusion_mode: str, params: Params, dev, ext):
    """One batched forward over a packed request batch (jitted; the
    bucket policy bounds how many distinct shapes ever get traced)."""
    buf = execute(fn, params, dev, ext, fusion_mode=fusion_mode).buf
    return readout_roots(buf, dev)


def _vertex_tick(fn, spec, params: Params, buf: jax.Array,
                 child_ids: jax.Array, child_mask: jax.Array,
                 ext_rows: jax.Array, node_mask: jax.Array,
                 offset: jax.Array) -> jax.Array:
    """One decode batching task over the slot pool (jitted once; slot
    occupancy, positions and the ping-pong offset are all data)."""
    M = child_ids.shape[0]
    ext = fn.project_inputs(params, ext_rows)          # hoisted eager prefix
    # Slot m pulls row m directly (inactive slots already carry zero
    # rows, built host-side) — no ext sentinel needed on this path.
    ext_ids = jnp.arange(M, dtype=jnp.int32)
    if spec is not None:
        # The slot pool is 2M+1 rows: relaying it out per tick is cheap.
        return from_rows(kops.level_megastep(
            spec.kind, as_rows(buf), child_ids, child_mask, ext_ids,
            node_mask, offset, as_rows(ext), spec.weights(params)))
    S = buf.shape[1]
    ch = jnp.take(buf, child_ids.reshape(-1), axis=0).reshape(M, 1, S)
    io = VertexIO(child_states=ch, child_mask=child_mask.astype(buf.dtype),
                  external=ext,
                  node_mask=node_mask.astype(buf.dtype))
    out = fn.apply(params, io)
    state = (out.state * io.node_mask[:, None]).astype(buf.dtype)
    return jax.lax.dynamic_update_slice(buf, state, (offset, 0))
