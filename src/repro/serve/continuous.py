"""Continuous cross-request batching: ONE live frontier over all
in-flight graphs.

The engines in ``serve/engine.py`` batch at request granularity: the
slot-pool engines advance co-resident *sequences* in lockstep, and
``StructureServeEngine`` scores whole batches — frontier rows idle
whenever graphs finish at different depths, and a request arriving
mid-batch waits for the next flush.  :class:`ContinuousBatchEngine` is
the LLM-style fix, DyNet's agenda-based autobatching (PAPERS.md,
arxiv 1701.03980) executed through the fused megastep:

  - **one agenda** — every in-flight graph's vertices live in a shared
    arena buffer ``[num_rows + 1, S]`` (last row = zero sentinel); a
    request is admitted by allocating arena rows from a free list and
    translating its cached per-topology plan into arena coordinates —
    pure host-side data, the compiled program never changes;
  - **union-frontier ticks** — each tick fires ONE fused megastep
    (``core.scheduler.frontier_step`` → ``kops.frontier_megastep``)
    over the ready vertices of ALL in-flight graphs, each row at its
    own depth, writing to per-row arena destinations.  Up to
    ``AdmissionPolicy.max_window`` ticks are planned host-side and
    dispatched as one ``lax.scan`` window (one XLA call), bounded by
    the first retirement so finished roots free rows promptly;
  - **mid-flight admission** — new requests enter whenever rows free
    up, FIFO with head-of-line blocking (a big graph never starves);
    PR 6's :class:`~repro.serve.robustness.RequestLifecycle` supplies
    backpressure, TTL deadlines and the exactly-one-terminal-status
    invariant unchanged;
  - **deadline-aware flushing** — ``step()`` defers firing a sparse
    frontier (waiting for arrivals to fill it) only while no live
    deadline is within ``ttl_slack_s`` and at most ``max_defer_ticks``
    times; near a deadline the window shrinks to single ticks so
    timeouts are enforced at tick granularity (the latency-vs-occupancy
    trade, JIT dynamic batching's cost model, arxiv 1904.07421);
  - **immediate retirement into readout heads** — finished roots are
    read back the window they complete, non-finite roots fail alone,
    and the rest go straight through ``models/readout.py``: batched
    classification/regression logits, and optionally the
    sampled-feedback :class:`~repro.models.readout.TokenReadout` loop
    (rng folded per request id — tokens are deterministic no matter how
    requests interleave).

**Bit-identity contract** (the property the test suite proves on both
``REPRO_FUSION`` legs): every request's root state — and its readout
logits — is bit-identical to scoring that request ALONE through
``StructureServeEngine``.  This holds because (a) the per-row math of
``frontier_step`` is exactly the level scan's on the matching fusion
leg, (b) inputs are projected at admission over the same padded
``[N + 1, X]`` matrix solo scoring projects, and (c) XLA's row-wise
arithmetic is batch-width-invariant, so co-tenants never perturb a
row's bits.  Continuous batching is therefore a pure throughput/latency
optimization — never an accuracy trade.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scheduler import frontier_step, resolve_fusion
from repro.core.structure import InputGraph, LevelSchedule
from repro.core.vertex import has_eager_projection
from repro.dist.fault import chaos_corrupt_ext, chaos_fire
from repro.kernels.level_megastep import as_rows, from_rows
from repro.models.readout import ClassificationHead, TokenReadout
from repro.obs import trace
from repro.pipeline import BucketPolicy, ScheduleCache
from repro.serve.engine import _EngineBase
from repro.serve.robustness import (ACTIVE, CircuitBreaker,
                                    RequestLifecycle, validate_structure)

Params = Any


@dataclasses.dataclass
class ContinuousRequest:
    """One structure to score continuously: topology ``G`` + per-node
    inputs ``[num_nodes, X_raw]``.  The engine fills ``root_state``
    (always), ``logits``/``label`` (when it has a head) and ``tokens``
    (when it has a token readout)."""

    request_id: int
    graph: InputGraph
    inputs: np.ndarray
    ttl: Optional[float] = None      # seconds from submit to deadline
    # -- filled by the engine ------------------------------------------
    root_state: Optional[np.ndarray] = None
    logits: Optional[np.ndarray] = None
    label: Optional[int] = None
    tokens: Optional[List[int]] = None
    done: bool = False
    status: str = "new"              # lifecycle: serve/robustness.py
    error: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class AdmissionPolicy:
    """The latency-vs-occupancy knobs of :meth:`ContinuousBatchEngine.step`.

    ``min_occupancy`` — fire immediately once the next tick's frontier
    is at least this full; below it the engine may *defer* (skip the
    tick, letting arrivals accumulate) up to ``max_defer_ticks``
    consecutive times.  ``ttl_slack_s`` — once any live request's
    deadline is within this slack, never defer AND shrink the dispatch
    window to single ticks (deadline enforcement at tick granularity).
    ``max_window`` — maximum ticks planned host-side and dispatched as
    one ``lax.scan`` call (amortizes dispatch overhead; windows also
    stop at the first retirement so finished roots free rows promptly).
    """

    min_occupancy: float = 0.5
    ttl_slack_s: float = 0.05
    max_defer_ticks: int = 4
    max_window: int = 8


@dataclasses.dataclass
class _Plan:
    """Frontier plan of one topology in SOLO-slot space (cached per
    fingerprint): per real level, the occupied slots, their child ids /
    mask, and their external-row ids.  Arena translation at admission
    is a handful of vectorized fancy-index ops."""

    levels: List[Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]
    root_slot: int
    num_rows: int                    # real vertices = arena rows needed
    sentinel_slot: int               # T*M (solo buffer sentinel)
    n_pad: int                       # padded node count N (ext is [N+1, X])


@dataclasses.dataclass(frozen=True)
class _ExtShim:
    """What ``chaos_corrupt_ext`` hooks read off a schedule: the padded
    per-sample node count (K=1 on the admission path)."""

    N: int


class _Active:
    """One in-flight request: its arena-space plan plus the frontier
    cursor (level index + lane offset within the level — partial levels
    split across ticks when the frontier is full)."""

    __slots__ = ("req", "levels", "level_idx", "lane_idx", "root_row",
                 "rows")

    def __init__(self, req: ContinuousRequest,
                 levels: List[Tuple[np.ndarray, np.ndarray, np.ndarray,
                                    np.ndarray]],
                 root_row: int, rows: np.ndarray):
        self.req = req
        self.levels = levels          # per level: (dest, cids, cmask, ext)
        self.level_idx = 0
        self.lane_idx = 0
        self.root_row = root_row
        self.rows = rows

    @property
    def finished(self) -> bool:
        return self.level_idx >= len(self.levels)


def _plan_from_schedule(sched: LevelSchedule) -> _Plan:
    """Project a solo (K=1) packed schedule down to its real lanes."""
    T, M = sched.T, sched.M
    levels = []
    total = 0
    for t in range(T):
        lanes = np.nonzero(sched.node_mask[t] > 0)[0]
        if lanes.size == 0:
            continue                  # bucket-padded empty level
        levels.append(((t * M + lanes).astype(np.int64),
                       sched.child_ids[t][lanes].astype(np.int64),
                       sched.child_mask[t][lanes].astype(np.float32),
                       sched.ext_ids[t][lanes].astype(np.int64)))
        total += int(lanes.size)
    return _Plan(levels=levels, root_slot=int(sched.root_slots[0]),
                 num_rows=total, sentinel_slot=T * M,
                 n_pad=int(sched.N))


def _frontier_window(fn, spec, params: Params, buf: jax.Array,
                     child_ids: jax.Array, child_mask: jax.Array,
                     ext_rows: jax.Array, node_mask: jax.Array,
                     out_ids: jax.Array) -> jax.Array:
    """``k`` union-frontier ticks as one ``lax.scan`` (jitted once per
    window length; occupancy, depths and destinations are all data).
    The fused leg carries the arena in the kernels' row layout for the
    whole window, so it is relaid out once per window, not per tick."""

    def body(b, xs):
        cid, cm, er, nm, oid = xs
        return frontier_step(fn, params, b, cid, cm, er, nm, oid,
                             spec=spec), None

    if spec is not None:
        buf = as_rows(buf)
    buf, _ = jax.lax.scan(body, buf, (child_ids, child_mask, ext_rows,
                                      node_mask, out_ids))
    return from_rows(buf) if spec is not None else buf


class ContinuousBatchEngine(_EngineBase):
    """Continuous cross-request batching over one live frontier agenda.

    ``num_rows`` — arena capacity (total co-resident vertices across
    all in-flight graphs); ``frontier_width`` — lanes per tick (the
    ``M`` of the compiled frontier program).  ``head`` /
    ``token_readout`` attach retirement-time readouts (pass their
    params alongside).  Everything else mirrors the other engines:
    bounded queue, TTLs, fused→oracle degradation ladder with a circuit
    breaker, non-finite root guard.
    """

    def __init__(self, fn, params: Params, *, num_rows: int = 256,
                 frontier_width: int = 32, fusion_mode: str = "auto",
                 policy: AdmissionPolicy = AdmissionPolicy(),
                 head: Optional[ClassificationHead] = None,
                 head_params: Optional[Params] = None,
                 token_readout: Optional[TokenReadout] = None,
                 token_params: Optional[Params] = None,
                 max_new_tokens: int = 16,
                 rng: Optional[jax.Array] = None,
                 max_queue: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic,
                 breaker_threshold: int = 3,
                 guard_nonfinite: bool = True,
                 cache: Optional[ScheduleCache] = None):
        if num_rows < 1 or frontier_width < 1:
            raise ValueError("num_rows and frontier_width must be >= 1")
        self.fn = fn
        self.params = params
        self.num_rows = num_rows
        self.frontier_width = frontier_width
        self.policy = policy
        self.A = max(1, getattr(fn, "arity", 1))
        self.spec = resolve_fusion(fn, fusion_mode, sched_arity=self.A)
        self._fusion = fusion_mode
        self.head = head
        self.head_params = head_params
        self.token_readout = token_readout
        self.token_params = token_params
        self.max_new_tokens = max_new_tokens
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.guard_nonfinite = guard_nonfinite
        self.lifecycle = RequestLifecycle(max_queue=max_queue, clock=clock)
        self._breaker = CircuitBreaker(breaker_threshold)
        # Per-request schedule reuse: solo schedules come from the
        # ScheduleCache's per-GRAPH tier — a recurring topology admits
        # with ZERO packing work, and one seen ANYWHERE (any cold batch
        # pack harvests its members; any persist store survives
        # restarts) admits without a solo pack.  The derived frontier
        # plan is memoized in the graph-tier entry's ``extras``, so
        # plan lifetime tracks schedule lifetime (no private LRU).
        self.cache = cache if cache is not None else ScheduleCache()
        self._buckets = BucketPolicy(mode="pow2")
        self.plan_hits = 0
        self.plan_misses = 0
        # Arena: rows [0, num_rows) are allocatable; row num_rows is the
        # zero sentinel absent children gather (it is never written —
        # pad lanes scatter out of range and are dropped).
        S = fn.state_dim
        self._buf = jnp.zeros((num_rows + 1, S), jnp.float32)
        self._free: List[int] = list(range(num_rows - 1, -1, -1))
        self._active: List[_Active] = []
        self._project = (jax.jit(fn.project_inputs)
                         if has_eager_projection(fn) else None)
        self._window = jax.jit(functools.partial(_frontier_window, fn,
                                                 self.spec))
        self._window_oracle = jax.jit(functools.partial(_frontier_window,
                                                        fn, None))
        self._zero_dropped = jax.jit(
            lambda buf, keep: jnp.where(keep[:, None], buf, 0.0))
        self._head_logits = (jax.jit(head.logits) if head is not None
                             else None)
        self.ticks = 0
        self.windows = 0
        self.deferred = 0
        self._defer_run = 0

    # -- ingress ------------------------------------------------------------
    @property
    def fused(self) -> bool:
        """True while windows attempt the fused frontier megastep (False
        once the circuit breaker has pinned the op-by-op oracle)."""
        return self.spec is not None and not self._breaker.open

    @property
    def num_active(self) -> int:
        return len(self._active)

    @property
    def free_rows(self) -> int:
        return len(self._free)

    def submit(self, req: ContinuousRequest) -> bool:
        """Validate + enqueue; returns False (and routes ``req`` to the
        ``rejected`` terminal) on a malformed structure, non-finite
        inputs, a structure exceeding the arena capacity or the
        engine's gather arity, a full queue, or a double-submitted
        request object."""
        err = validate_structure(req.graph, req.inputs, self.fn.input_dim)
        if err is None and req.graph.num_nodes > self.num_rows:
            err = (f"structure needs {req.graph.num_nodes} arena rows > "
                   f"engine num_rows={self.num_rows}")
        if err is None and req.graph.max_arity > self.A:
            err = (f"structure arity {req.graph.max_arity} > engine "
                   f"gather arity {self.A}")
        if err is not None:
            err = f"request {req.request_id}: {err}"
        return self.lifecycle.submit(req, err)

    # -- one engine step -----------------------------------------------------
    def step(self) -> int:
        """Admit waiting requests into free rows, then either fire one
        dispatch window over the union frontier or (policy permitting)
        defer to let the frontier fill.  Returns live requests (active +
        queued) after the step."""
        with trace.span("cb.tick", active=self.num_active,
                        queued=len(self.queue)):
            return self._step()

    def _step(self) -> int:
        self.lifecycle.sweep_deadlines()
        self._retire_expired()
        self._admit()
        if not self._active:
            self._defer_run = 0
            return len(self.queue)

        now = self.lifecycle.clock()
        urgent = self._min_slack(now) <= self.policy.ttl_slack_s
        occ = self._next_tick_lanes() / float(self.frontier_width)
        if (occ < self.policy.min_occupancy and not urgent
                and self._defer_run < self.policy.max_defer_ticks):
            # Partial frontier and no deadline pressure: hold the tick
            # so arrivals between steps can fill it (bounded — the
            # frontier never starves behind the occupancy target).
            self._defer_run += 1
            self.deferred += 1
            return len(self._active) + len(self.queue)
        self._defer_run = 0

        window = 1 if urgent else self.policy.max_window
        with trace.span("cb.plan"):
            ticks, done = self._plan_window(window)
        if ticks:
            with trace.span("cb.stack", ticks=len(ticks)):
                args = self._stack_window(ticks)
            try:
                with trace.span("cb.window", ticks=len(ticks),
                                fused=self.fused):
                    self._buf = trace.maybe_block(self._run_window(args))
            except Exception as e:       # noqa: BLE001 — oracle failed too
                # Both rungs of the ladder failed: the window is lost
                # (the buffer was not advanced), so every in-flight
                # request reaches the ``failed`` terminal; queued
                # requests are untouched and admit next step.
                self._fail_inflight(f"frontier window failed: {e}")
                return len(self._active) + len(self.queue)
            self.ticks += len(ticks)
            self.windows += 1
        if done:
            with trace.span("cb.retire", count=len(done)):
                self._retire(done)
        return len(self._active) + len(self.queue)

    def run(self, max_steps: int = 100_000) -> List[ContinuousRequest]:
        """Drain the queue; returns finished requests."""
        for _ in range(max_steps):
            if self.step() == 0:
                break
        return self.finished

    # -- admission -----------------------------------------------------------
    def _admit(self) -> int:
        """FIFO admission into free arena rows.  Head-of-line blocking
        is deliberate: a wide graph waits for rows rather than being
        overtaken forever by small ones (no starvation)."""
        admitted = 0
        while self.queue:
            req = self.queue[0]
            try:
                plan = self._plan_for(req.graph)
            except Exception as e:       # noqa: BLE001 — pack fault
                # Poisoned topology fails ALONE at admission — with
                # per-request schedules there is nothing to bisect.
                self.queue.pop(0)
                self.lifecycle.finish_failed(req, f"schedule pack "
                                                  f"failed: {e}")
                continue
            if plan.num_rows > len(self._free):
                break
            self.queue.pop(0)
            try:
                with trace.correlate(request=req.request_id), \
                        trace.span("cb.admit", rows=plan.num_rows):
                    self._activate(req, plan)
            except Exception as e:       # noqa: BLE001 — ext/projection
                self.lifecycle.finish_failed(req, f"admission failed: {e}")
                continue
            admitted += 1
        return admitted

    def _plan_for(self, graph: InputGraph) -> _Plan:
        pads = self._buckets.bucket([graph])._replace(arity=self.A)
        sched, extras = self.cache.get_or_pack_graph(
            graph, tuple(pads), with_runs=False, with_extras=True)
        plan = extras.get("frontier_plan")
        if plan is not None:
            self.plan_hits += 1
            return plan
        self.plan_misses += 1
        plan = _plan_from_schedule(sched)
        extras["frontier_plan"] = plan
        return plan

    def _activate(self, req: ContinuousRequest, plan: _Plan) -> None:
        """Allocate arena rows and translate the solo-slot plan into
        arena coordinates; gather (and, for GateSpec cells, eagerly
        project) the request's external rows once."""
        rows = np.asarray([self._free.pop() for _ in range(plan.num_rows)],
                          np.int64)
        arena_of = np.full(plan.sentinel_slot + 1, self.num_rows, np.int64)
        arena_of[np.concatenate([lv[0] for lv in plan.levels])] = rows
        ext = self._ext_matrix(req, plan)
        levels = []
        for slots, cids, cmask, eids in plan.levels:
            levels.append((arena_of[slots], arena_of[cids], cmask,
                           ext[eids]))
        req.status = ACTIVE
        self._active.append(_Active(req, levels,
                                    int(arena_of[plan.root_slot]), rows))

    def _ext_matrix(self, req: ContinuousRequest, plan: _Plan) -> np.ndarray:
        """The request's packed ``[N + 1, X]`` external matrix, eagerly
        projected when the cell declares a projection — the SAME padded
        shape and the same one-matmul hoist solo scoring performs, so
        every pulled row is bitwise what solo scoring pulls."""
        raw = np.zeros((plan.n_pad + 1, self.fn.input_dim), np.float32)
        x = np.asarray(req.inputs, np.float32)
        raw[: x.shape[0]] = x
        raw = chaos_corrupt_ext(raw, _ExtShim(plan.n_pad))
        if self._project is not None:
            return np.asarray(self._project(self.params, jnp.asarray(raw)))
        return raw

    # -- window planning ------------------------------------------------------
    def _next_tick_lanes(self) -> int:
        avail = 0
        for a in self._active:
            if not a.finished:
                avail += len(a.levels[a.level_idx][0]) - a.lane_idx
                if avail >= self.frontier_width:
                    return self.frontier_width
        return avail

    def _min_slack(self, now: float) -> float:
        slack = float("inf")
        for a in self._active:
            d = getattr(a.req, "_deadline", None)
            if d is not None:
                slack = min(slack, d - now)
        return slack

    def _plan_window(self, max_ticks: int):
        """Simulate up to ``max_ticks`` union-frontier ticks host-side.
        Each tick takes lanes from every active request's CURRENT level
        (levels never merge within a tick — a vertex's children must be
        written by an earlier tick), splitting a level across ticks
        when the frontier is full.  Stops at the first tick that
        completes a request, so retirement (and row reuse) is prompt.
        Returns ``(ticks, done)``: per-tick concatenated lane arrays
        and the actives that finished."""
        M = self.frontier_width
        cursor = {id(a): (a.level_idx, a.lane_idx) for a in self._active}
        ticks = []
        done: List[_Active] = []
        for _ in range(max_ticks):
            parts = []
            used = 0
            advanced = []
            for a in self._active:
                li, lo = cursor[id(a)]
                if li >= len(a.levels):
                    continue
                dest, cids, cmask, ext = a.levels[li]
                take = min(len(dest) - lo, M - used)
                if take <= 0:
                    continue
                parts.append((dest[lo: lo + take], cids[lo: lo + take],
                              cmask[lo: lo + take], ext[lo: lo + take]))
                used += take
                if lo + take >= len(dest):
                    cursor[id(a)] = (li + 1, 0)
                else:
                    cursor[id(a)] = (li, lo + take)
                advanced.append(a)
                if used >= M:
                    break
            if not parts:
                break
            ticks.append(parts)
            finished = [a for a in advanced
                        if cursor[id(a)][0] >= len(a.levels)]
            if finished:
                done.extend(finished)
                break
        # Commit the simulated cursors for the ticks actually planned.
        for a in self._active:
            a.level_idx, a.lane_idx = cursor[id(a)]
        return ticks, done

    def _stack_window(self, ticks) -> Tuple:
        """Pad each planned tick to the fixed frontier shape and stack
        the window: ``[k, M, ...]`` device arrays for one scan call.
        Pad lanes gather the sentinel, scatter out of range (unique ids
        past the arena — dropped), and carry node_mask 0."""
        M, A = self.frontier_width, self.A
        G = self._ext_width()
        k = len(ticks)
        child_ids = np.full((k, M, A), self.num_rows, np.int32)
        child_mask = np.zeros((k, M, A), np.float32)
        ext_rows = np.zeros((k, M, G), np.float32)
        node_mask = np.zeros((k, M), np.float32)
        out_ids = np.tile(self.num_rows + 1 + np.arange(M, dtype=np.int32),
                          (k, 1))
        for t, parts in enumerate(ticks):
            o = 0
            for dest, cids, cmask, ext in parts:
                n = len(dest)
                out_ids[t, o: o + n] = dest
                child_ids[t, o: o + n] = cids
                child_mask[t, o: o + n] = cmask
                ext_rows[t, o: o + n] = ext
                node_mask[t, o: o + n] = 1.0
                o += n
        return (self.params, self._buf, jnp.asarray(child_ids),
                jnp.asarray(child_mask), jnp.asarray(ext_rows),
                jnp.asarray(node_mask), jnp.asarray(out_ids))

    def _ext_width(self) -> int:
        return self.fn.ext_dim

    def _run_window(self, args: Tuple) -> jax.Array:
        """One window through the degradation ladder: fused frontier
        megasteps first; on failure fall back to the op-by-op oracle
        for THIS window, and once the breaker trips, pin the oracle."""
        if self.fused:
            try:
                chaos_fire("kernel")
                out = self._window(*args)
                out.block_until_ready()  # surface async kernel failures
                self._breaker.record_success()
                return out
            except Exception as e:       # noqa: BLE001 — degrade
                self._breaker.record_failure()
                self.lifecycle.degrade(e)
        return self._window_oracle(*args)

    # -- retirement -----------------------------------------------------------
    def _retire_expired(self) -> None:
        """Retire in-flight requests whose deadline passed; their arena
        rows return to the free list ZEROED (freed rows must never leak
        a dead request's states into the pool)."""
        expired = [a for a in self._active
                   if self.lifecycle.expired(a.req)]
        if not expired:
            return
        for a in expired:
            self.lifecycle.finish_timeout(a.req)
        self._release(expired)

    def _fail_inflight(self, reason: str) -> None:
        for a in self._active:
            self.lifecycle.finish_failed(a.req, reason)
        self._release(self._active)

    def _release(self, acts: List[_Active]) -> None:
        """Free (and zero) the arena rows of retired requests.  Zeroing
        goes through a fixed-shape keep-mask ``where`` (one compile for
        the engine's lifetime) — a variable-length ``.at[rows].set``
        would recompile the eager scatter for every retirement count.
        ``where`` passes kept rows through bitwise."""
        rows = np.concatenate([a.rows for a in acts]) if acts else None
        self._active = [a for a in self._active if a not in acts]
        if rows is not None and rows.size:
            keep = np.ones(self.num_rows + 1, bool)
            keep[rows] = False
            self._buf = self._zero_dropped(self._buf, jnp.asarray(keep))
            self._free.extend(int(r) for r in rows)

    def _retire(self, done: List[_Active]) -> None:
        """Read back finished roots and route them through the readout
        heads — the lazy ``push`` made immediate.  One whole-buffer
        host readback, indexed in numpy: a per-count device gather
        would recompile for every retirement batch size."""
        with trace.span("cb.readback", count=len(done)):
            buf_np = np.asarray(self._buf)
        roots = buf_np[[a.root_row for a in done]]
        ok: List[ContinuousRequest] = []
        for a, root in zip(done, roots):
            req = a.req
            if self.lifecycle.expired(req):
                self.lifecycle.finish_timeout(req)
                status = "timeout"
            elif self.guard_nonfinite and not np.isfinite(root).all():
                self.lifecycle.finish_failed(req, "non-finite root state")
                status = "failed"
            else:
                req.root_state = root.copy()
                ok.append(req)
                status = "ok"
            trace.instant("cb.retired", request=req.request_id,
                          status=status)
        self._release(done)
        if ok and self._head_logits is not None:
            # Batched readout, padded to a power of two so the jitted
            # head compiles per bucket, not per retirement count.
            K = len(ok)
            Kp = 1 << (K - 1).bit_length()
            batch = np.zeros((Kp, self.fn.state_dim), np.float32)
            for i, req in enumerate(ok):
                batch[i] = req.root_state
            logits = np.asarray(self._head_logits(self.head_params,
                                                  jnp.asarray(batch)))
            for i, req in enumerate(ok):
                req.logits = logits[i].copy()
                req.label = int(np.argmax(logits[i]))
        if ok and self.token_readout is not None:
            for req in ok:
                req.tokens = self.token_readout.generate(
                    self.token_params, self.params, req.root_state,
                    jax.random.fold_in(self.rng, req.request_id),
                    max_tokens=self.max_new_tokens)
        for req in ok:
            self.lifecycle.finish_ok(req)

    # -- health ---------------------------------------------------------------
    def _health_extra(self) -> Dict[str, Any]:
        return {"active_requests": self.num_active,
                "free_rows": self.free_rows,
                "num_rows": self.num_rows,
                "frontier_width": self.frontier_width,
                "ticks": self.ticks, "windows": self.windows,
                "deferred": self.deferred,
                "plan_hits": self.plan_hits,
                "plan_misses": self.plan_misses,
                "breaker_open": self._breaker.open,
                "breaker_trips": self._breaker.trips}
