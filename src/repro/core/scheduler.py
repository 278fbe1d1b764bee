"""The Cavs scheduler: batched level-synchronous execution (paper Alg. 1).

Forward: one ``lax.scan`` step per batching task ``V_t`` — gather child
states from the node-state buffer, apply the static vertex function ``F``
once over all ``M`` slots, scatter the results into the buffer block
``[t*M, (t+1)*M)`` (the dynamic-tensor offset discipline, §3.3).

Backward: two modes.

* ``grad_mode="scan"`` — plain ``jax.grad`` through the scan.  XLA's scan
  transpose saves per-step residuals and replays them in exact reverse
  order: this *is* the paper's task stack ``S`` (Alg. 1 BACKWARD), and the
  transpose of the buffer ``take`` *is* the ``∂gather = scatter`` rule
  (§3.4).

* ``grad_mode="lazy"`` — the paper's *lazy batching* (§3.5): the reverse
  sweep propagates only the state-chain cotangents; the parameter
  gradients (the paper's canonical lazy operators: "the math operators
  for computing gradients of the model parameters") are computed **once,
  batched over all vertices of all graphs**, as a single flat VJP over
  the ``T*M`` node slots, instead of ``T`` per-task VJPs.  As a bonus the
  forward saves only the node buffer (activations inside ``F`` are
  recomputed), so this doubles as a rematerialization policy.

The *eager* side of §3.5 (streaming) is ``hoist=True``: when ``F``
declares ``project_inputs`` (its vertex-independent prefix, e.g. the
``W·x`` input projections), it is evaluated for ALL external rows in one
batched call *before* the sequential region.

Fused megasteps (``fusion_mode``): cells that declare a
:class:`~repro.core.vertex.GateSpec` (the whole zoo: LSTM, GRU,
Tree-LSTM, Tree-FC) can route each batching task through ONE fused
kernel launch (``kernels/level_megastep.py``) instead of gather →
apply → scatter as three XLA ops: scalar-prefetched ``child_ids``
drive the gather DMA, the gate math stays VMEM-resident, and the
contiguous block write aliases the buffer in place across the scan —
no per-level HBM round-trip of the ``[M, A, S]`` child states or the
``[M, G]`` gate lanes.  ``fusion_mode="auto"`` (default; overridable
via the ``REPRO_FUSION`` env var) fuses whenever the cell supports it
(including the fixed-arity check for Tree-FC's concat weight);
``"none"`` keeps the op-by-op path (the correctness oracle and ablation
baseline); ``"megastep"`` requires fusion and raises when unsupported.
The fused path carries its own custom VJP, and its reverse sweep now
mirrors the forward megastep: each reverse level is ONE fused op
(``kops.bwd_megastep``) that recomputes the level's gates from the
residual node buffer, applies the cotangent math for the declared
kind, and scatter-ADDs the child-row cotangents into the carried
gradient buffer (∂gather = scatter-add, §3.4) — on the pallas backend
a single launch per level (``kernels/level_megastep_bwd.bwd_megastep``)
with the gradient buffer aliased in place; off-pallas the jnp
``level_bwd`` sweep, which stays the correctness oracle and ablation
baseline (selectable via ``REPRO_FUSION=none`` /
``REPRO_KERNEL_IMPL=chunked``).  The parameter/external gradients are
computed lazily in one flat batched pass (§3.5) — so both
:func:`execute` and :func:`execute_lazy` share one backward, with
activations recomputed from the node buffer (remat).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.structure import DeviceSchedule, InputGraph, LevelSchedule
from repro.core.vertex import (GateSpec, VertexFunction, VertexIO,
                               VertexOutput, apply_unbatched,
                               get_gate_spec, has_eager_projection)
from repro.kernels import level_megastep as megastep
from repro.kernels import ops as kops

Params = Any
Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ExecResult:
    """Outcome of scheduling ``F`` over a packed batch of graphs.

    ``buf``: ``[T*M + 1, S]`` node-state buffer (row ``T*M`` = sentinel).
    ``pushed``: ``[T*M, O]`` per-slot pushed outputs, or ``None``.
    """

    buf: Array
    pushed: Optional[Array] = None


# ---------------------------------------------------------------------------
# Level utilities
# ---------------------------------------------------------------------------

def _level_io(buf: Array, external: Array, child_ids: Array,
              child_mask: Array, ext_ids: Array, node_mask: Array,
              state_dim: int) -> VertexIO:
    """Materialize the VertexIO of one batching task from the buffer.

    ``jnp.take`` on the buffer is the Cavs ``gather`` primitive (its VJP
    is the scatter-add that §3.4 prescribes); the take on ``external`` is
    ``pull``.
    """
    M, A = child_ids.shape
    ch = jnp.take(buf, child_ids.reshape(-1), axis=0).reshape(M, A, state_dim)
    ext = jnp.take(external, ext_ids, axis=0)
    return VertexIO(child_states=ch, child_mask=child_mask.astype(buf.dtype),
                    external=ext, node_mask=node_mask.astype(buf.dtype))


def _maybe_hoist(fn: VertexFunction, params: Params, external: Array,
                 hoist: bool) -> Tuple[Array, bool]:
    """If ``F`` declares an eager prefix and hoisting is on, project ALL
    external rows in one batched call (streaming, §3.5).  Returns the
    external matrix plus whether projection still needs to happen
    per-level (hoisting ablated OFF)."""
    if has_eager_projection(fn):
        if hoist:
            return fn.project_inputs(params, external), False
        return external, True
    return external, False


# ---------------------------------------------------------------------------
# Fused megastep path (one launch per batching task; custom VJP)
# ---------------------------------------------------------------------------

def _fusion_spec(fn: VertexFunction, fusion_mode: str, *, hoist: bool,
                 collect_push: bool, dtype=jnp.float32,
                 sched_arity: Optional[int] = None) -> Optional[GateSpec]:
    """Resolve the fusion decision: the cell's GateSpec when the fused
    megastep path applies, else ``None`` (op-by-op path).

    The fused buffer dtype follows the hoisted projection (float32 for
    every cell in the zoo), so a non-f32 ``dtype`` request falls back
    to the op-by-op path under "auto" and raises under "megastep".
    Fixed-arity kinds (Tree-FC's concat weight) additionally require the
    packed schedule's ``A`` to match ``spec.arity`` exactly.
    """
    mode = fusion_mode
    if mode == "auto":
        mode = os.environ.get("REPRO_FUSION", "auto")
    if mode not in ("auto", "megastep", "none"):
        raise ValueError(f"fusion_mode must be 'auto', 'megastep' or "
                         f"'none', got {mode!r}")
    if mode == "none":
        return None
    spec = get_gate_spec(fn)
    f32 = jnp.dtype(dtype) == jnp.float32
    arity_ok = (spec is None or spec.arity is None or sched_arity is None
                or spec.arity == sched_arity)
    ok = (spec is not None and has_eager_projection(fn) and hoist
          and not collect_push and f32 and arity_ok)
    if mode == "megastep" and not ok:
        if spec is not None and not arity_ok:
            raise ValueError(
                f"fusion_mode='megastep': {type(fn).__name__} declares a "
                f"fixed gather arity {spec.arity} but the packed schedule "
                f"has A={sched_arity} — repack with pad_arity="
                f"{spec.arity} or use fusion_mode='none'")
        raise ValueError(
            "fusion_mode='megastep' needs a cell with a GateSpec and an "
            "eager projection, hoist=True, collect_push=False and a "
            f"float32 buffer dtype (got fn={type(fn).__name__}, "
            f"hoist={hoist}, collect_push={collect_push}, dtype={dtype})")
    return spec if ok else None


def resolve_fusion(fn: VertexFunction, fusion_mode: str = "auto", *,
                   hoist: bool = True, collect_push: bool = False,
                   dtype=jnp.float32,
                   sched_arity: Optional[int] = None) -> Optional[GateSpec]:
    """Public fusion resolution (used by ``serve.engine`` and tooling):
    the GateSpec the fused path will use, or ``None`` for op-by-op —
    the same decision :func:`execute` makes internally."""
    return _fusion_spec(fn, fusion_mode, hoist=hoist,
                        collect_push=collect_push, dtype=dtype,
                        sched_arity=sched_arity)


def _megastep_scan(spec: GateSpec, weights, sched: DeviceSchedule,
                   ext: Array, dtype) -> Array:
    """Forward scan where each batching task is ONE fused megastep: the
    buffer is carried (and, on the pallas backend, aliased) in place.
    Takes ``ext`` and returns the buffer in the row layout
    ``[·, 1, ·]`` the kernels DMA rows of (``megastep.as_rows``)."""
    T, M = sched.T, sched.M
    S = spec.state_dim
    buf0 = jnp.zeros((T * M + 1, 1, S), dtype)

    def step(buf, xs):
        t, child_ids, child_mask, ext_ids, node_mask = xs
        buf = kops.level_megastep(spec.kind, buf, child_ids, child_mask,
                                  ext_ids, node_mask, t * M, ext, weights)
        return buf, None

    xs = (jnp.arange(T, dtype=jnp.int32), sched.child_ids, sched.child_mask,
          sched.ext_ids, sched.node_mask)
    buf, _ = jax.lax.scan(step, buf0, xs)
    return buf


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _execute_megastep(fn: VertexFunction, params: Params, external: Array,
                      sched: DeviceSchedule) -> Array:
    """Fused forward (megastep per level) with the fused backward below.
    Returns the ``[T*M + 1, S]`` buffer; hoisting is always on."""
    spec = get_gate_spec(fn)
    ext = fn.project_inputs(params, external)
    return megastep.from_rows(_megastep_scan(
        spec, spec.weights(params), sched, megastep.as_rows(ext), ext.dtype))


def _megastep_fwd(fn, params, external, sched):
    ext, hoist_vjp = jax.vjp(
        lambda p, e: fn.project_inputs(p, e), params, external)
    spec = get_gate_spec(fn)
    buf = _megastep_scan(spec, spec.weights(params), sched,
                         megastep.as_rows(ext), ext.dtype)
    return megastep.from_rows(buf), (params, ext, buf, sched, hoist_vjp)


def _megastep_bwd(fn, res, g_buf):
    """The fused reverse: ONE launch per level for the state chain
    (recompute + cotangent math + ∂gather scatter-add fused,
    ``kops.bwd_megastep`` — §3.4) + ONE flat lazily-batched
    parameter/external gradient pass (§3.5).  Activations are
    recomputed from the saved node buffer (remat).  The residual
    buffer and the swept gradient buffer stay in the row layout."""
    params, ext, buf, sched, hoist_vjp = res
    spec = get_gate_spec(fn)
    weights = spec.weights(params)
    T, M, A = sched.T, sched.M, sched.A
    S = spec.state_dim
    ext_rows = megastep.as_rows(ext)

    # Sorted-run arrays travel with the schedule (precomputed host-side
    # in pack_batch) so the reverse scan body contains NO sort op; a
    # hand-built DeviceSchedule without them falls back to the kernel's
    # on-device argsort.
    have_runs = sched.sort_perm is not None \
        and sched.sorted_child_ids is not None and sched.run_head is not None

    def rev_step(g, xs):
        t, child_ids, child_mask, ext_ids, node_mask = xs[:5]
        sp, sc, rh = xs[5:] if have_runs else (None, None, None)
        # One fused reverse megastep: the level's state cotangent is
        # turned into child-row cotangents and scatter-ADDED into the
        # carried gradient buffer in place (on the pallas backend a
        # single launch mirroring the forward; off-pallas the jnp
        # ``level_bwd`` sweep — the correctness oracle).
        g = kops.bwd_megastep(spec.kind, g, buf, child_ids, child_mask,
                              ext_ids, node_mask, t * M, ext_rows, weights,
                              sort_perm=sp, sorted_child_ids=sc, run_head=rh)
        return g, None

    xs = (jnp.arange(T, dtype=jnp.int32), sched.child_ids, sched.child_mask,
          sched.ext_ids, sched.node_mask)
    if have_runs:
        xs = xs + (sched.sort_perm, sched.sorted_child_ids, sched.run_head)
    g_final, _ = jax.lax.scan(
        rev_step, megastep.as_rows(g_buf.astype(jnp.float32)), xs,
        reverse=True)
    # Row t*M+m reaches its final value before level t's reverse step
    # runs (all its parents live at levels > t), so the swept buffer IS
    # the per-slot state cotangent — no per-level stacking needed.
    g_state_flat = megastep.from_rows(g_final)[: T * M] \
        * sched.node_mask.reshape(T * M)[:, None].astype(g_final.dtype)

    # Lazy batching: one analytic pass over ALL T*M slots for the
    # parameter and pulled-row gradients.
    cid_flat = sched.child_ids.reshape(T * M, A)
    child_flat = jnp.take(buf, cid_flat.reshape(-1),
                          axis=0).reshape(T * M, A, S)
    rows_flat = jnp.take(ext, sched.ext_ids.reshape(T * M), axis=0)
    cmask_flat = sched.child_mask.reshape(T * M, A)
    _, d_gates, aux = megastep.level_bwd(spec.kind, g_state_flat, child_flat,
                                         rows_flat, cmask_flat, weights)
    w_grads = megastep.level_param_grads(spec.kind, d_gates, aux, weights)
    g_params = spec.inject_grads(params, w_grads)

    # ∂pull = push: scatter row cotangents back to the packed matrix,
    # then run the hoisted projection's VJP once.
    g_ext = jnp.zeros_like(ext).at[sched.ext_ids.reshape(T * M)].add(
        d_gates.astype(ext.dtype), mode="drop")
    g_params_hoist, g_external = hoist_vjp(g_ext)
    g_params = jax.tree.map(jnp.add, g_params, g_params_hoist)
    g_sched = jax.tree.map(_zero_ct, sched)
    return g_params, g_external, g_sched


_execute_megastep.defvjp(_megastep_fwd, _megastep_bwd)


# ---------------------------------------------------------------------------
# Batched forward (the paper's FORWARD, Alg. 1)
# ---------------------------------------------------------------------------

def execute(fn: VertexFunction, params: Params, sched: DeviceSchedule,
            external: Array, *, hoist: bool = True,
            collect_push: bool = False,
            dtype: jnp.dtype = jnp.float32,
            fusion_mode: str = "auto") -> ExecResult:
    """Run the batching policy over a packed minibatch of graphs.

    ``external``: ``[R + 1, X_raw]`` packed external inputs (last row is
    the zero sentinel).  Differentiable in ``params`` and ``external``.
    ``fusion_mode``: ``"auto"`` | ``"megastep"`` | ``"none"`` — see the
    module docstring; the fused path returns the same buffer to 1e-4.
    """
    spec = _fusion_spec(fn, fusion_mode, hoist=hoist,
                        collect_push=collect_push, dtype=dtype,
                        sched_arity=sched.A)
    if spec is not None:
        return ExecResult(buf=_execute_megastep(fn, params, external, sched))
    T, M = sched.T, sched.M
    S = fn.state_dim
    ext, project_per_level = _maybe_hoist(fn, params, external, hoist)
    buf0 = jnp.zeros((T * M + 1, S), dtype)

    def step(buf: Array, xs):
        t, child_ids, child_mask, ext_ids, node_mask = xs
        io = _level_io(buf, ext, child_ids, child_mask, ext_ids, node_mask, S)
        if project_per_level:
            # Streaming ablated off: the eager prefix runs inside the
            # sequential region, once per batching task.
            io = dataclasses.replace(
                io, external=fn.project_inputs(params, io.external))
        out = fn.apply(params, io)
        state = (out.state * io.node_mask[:, None]).astype(dtype)
        buf = jax.lax.dynamic_update_slice(buf, state, (t * M, 0))
        ys = out.push if collect_push else None
        return buf, ys

    xs = (jnp.arange(T, dtype=jnp.int32), sched.child_ids, sched.child_mask,
          sched.ext_ids, sched.node_mask)
    buf, pushes = jax.lax.scan(step, buf0, xs)
    pushed = None
    if collect_push and pushes is not None:
        pushed = pushes.reshape(T * M, -1)
    return ExecResult(buf=buf, pushed=pushed)


# ---------------------------------------------------------------------------
# Lazy-batched gradients (the paper's lazy batching, §3.5)
# ---------------------------------------------------------------------------

def _forward_buf(fn: VertexFunction, params: Params, sched: DeviceSchedule,
                 ext: Array, dtype) -> Array:
    """Forward scan producing only the node buffer (push unsupported here:
    in this framework pushes are realized as post-scan readouts, which is
    itself the lazy treatment of ``push``)."""
    T, M, S = sched.T, sched.M, fn.state_dim
    buf0 = jnp.zeros((T * M + 1, S), dtype)

    def step(buf, xs):
        t, child_ids, child_mask, ext_ids, node_mask = xs
        io = _level_io(buf, ext, child_ids, child_mask, ext_ids, node_mask, S)
        out = fn.apply(params, io)
        state = (out.state * io.node_mask[:, None]).astype(dtype)
        return jax.lax.dynamic_update_slice(buf, state, (t * M, 0)), None

    xs = (jnp.arange(T, dtype=jnp.int32), sched.child_ids, sched.child_mask,
          sched.ext_ids, sched.node_mask)
    buf, _ = jax.lax.scan(step, buf0, xs)
    return buf


def _flat_io(fn: VertexFunction, sched: DeviceSchedule, buf: Array,
             ext: Array) -> VertexIO:
    """One VertexIO covering ALL ``T*M`` slots at once (for the single
    batched parameter-gradient evaluation)."""
    T, M, A, S = sched.T, sched.M, sched.A, fn.state_dim
    flat_children = sched.child_ids.reshape(T * M, A)
    ch = jnp.take(buf, flat_children.reshape(-1), axis=0).reshape(T * M, A, S)
    e = jnp.take(ext, sched.ext_ids.reshape(T * M), axis=0)
    return VertexIO(child_states=ch,
                    child_mask=sched.child_mask.reshape(T * M, A).astype(buf.dtype),
                    external=e,
                    node_mask=sched.node_mask.reshape(T * M).astype(buf.dtype))


def _zero_ct(x):
    if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) or \
       jnp.issubdtype(jnp.asarray(x).dtype, jnp.complexfloating):
        return jnp.zeros_like(x)
    return np.zeros(jnp.shape(x), jax.dtypes.float0)


def execute_lazy(fn: VertexFunction, params: Params, external: Array,
                 sched: DeviceSchedule, fusion_mode: str = "auto") -> Array:
    """Like :func:`execute` (hoist on, no push) but with the lazy-batched
    backward.  Returns the ``[T*M + 1, S]`` buffer.

    With ``fusion_mode`` "auto"/"megastep" and a GateSpec-declaring
    cell, forward AND backward route through the fused megastep path
    (whose backward is itself lazy-batched); ``"none"`` keeps the
    op-by-op lazy path below as the ablation baseline.
    """
    spec = _fusion_spec(fn, fusion_mode, hoist=True, collect_push=False,
                        sched_arity=sched.A)
    if spec is not None:
        return _execute_megastep(fn, params, external, sched)
    return _execute_lazy_opbyop(fn, params, external, sched)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _execute_lazy_opbyop(fn: VertexFunction, params: Params, external: Array,
                         sched: DeviceSchedule) -> Array:
    """Op-by-op lazy path: scan of gather/apply/scatter ops with the
    flat lazy-batched parameter-gradient backward."""
    ext, _ = _maybe_hoist(fn, params, external, True)
    return _forward_buf(fn, params, sched, ext, ext.dtype)


def _lazy_fwd(fn, params, external, sched):
    ext, hoist_vjp = (external, None)
    if has_eager_projection(fn):
        ext, hoist_vjp = jax.vjp(
            lambda p, e: fn.project_inputs(p, e), params, external)
    buf = _forward_buf(fn, params, sched, ext, ext.dtype)
    return buf, (params, external, ext, buf, sched, hoist_vjp)


def _lazy_bwd(fn, res, g_buf):
    params, external, ext, buf, sched, hoist_vjp = res
    T, M, A, S = sched.T, sched.M, sched.A, fn.state_dim

    # -- reverse sweep: state-chain cotangents only (params closed over) --
    def rev_step(g, xs):
        t, child_ids, child_mask, ext_ids, node_mask = xs
        io = _level_io(buf, ext, child_ids, child_mask, ext_ids, node_mask, S)
        g_state = jax.lax.dynamic_slice(g, (t * M, 0), (M, S))
        g_state = g_state * io.node_mask[:, None]

        def f_of_children(ch):
            out = fn.apply(params, dataclasses.replace(io, child_states=ch))
            return out.state * io.node_mask[:, None]

        _, vjp_ch = jax.vjp(f_of_children, io.child_states)
        (g_ch,) = vjp_ch(g_state)
        g_ch = g_ch * io.child_mask[..., None]
        # ∂gather = scatter (§3.4): push child cotangents back into the buffer.
        g = g.at[child_ids.reshape(-1)].add(
            g_ch.reshape(M * A, S), mode="drop",
            unique_indices=False, indices_are_sorted=False)
        return g, g_state

    xs = (jnp.arange(T, dtype=jnp.int32), sched.child_ids, sched.child_mask,
          sched.ext_ids, sched.node_mask)
    _, g_states = jax.lax.scan(rev_step, g_buf, xs, reverse=True)
    g_state_flat = g_states.reshape(T * M, S)

    # -- lazy batching: ONE parameter/external VJP over all T*M slots ----
    io_flat = _flat_io(fn, sched, buf, ext)

    def f_flat(p, e_rows):
        out = fn.apply(p, dataclasses.replace(io_flat, external=e_rows))
        return out.state * io_flat.node_mask[:, None]

    _, vjp_flat = jax.vjp(f_flat, params, io_flat.external)
    g_params, g_ext_rows = vjp_flat(g_state_flat)

    # Scatter pulled-row cotangents back to the packed external matrix
    # (∂pull = push, §3.4).
    g_ext = jnp.zeros_like(ext).at[sched.ext_ids.reshape(T * M)].add(
        g_ext_rows, mode="drop")
    if hoist_vjp is not None:
        g_params_hoist, g_external = hoist_vjp(g_ext)
        g_params = jax.tree.map(jnp.add, g_params, g_params_hoist)
    else:
        g_external = g_ext
    g_sched = jax.tree.map(_zero_ct, sched)
    return g_params, g_external, g_sched


_execute_lazy_opbyop.defvjp(_lazy_fwd, _lazy_bwd)


# ---------------------------------------------------------------------------
# Union-frontier execution (continuous cross-request batching)
# ---------------------------------------------------------------------------

def frontier_step(fn: VertexFunction, params: Params, buf: Array,
                  child_ids: Array, child_mask: Array, ext_rows: Array,
                  node_mask: Array, out_ids: Array, *,
                  spec: Optional[GateSpec] = None) -> Array:
    """One batching task over a mixed-depth UNION frontier.

    The continuous serving engine schedules ready vertices of MANY
    in-flight graphs into one frontier: row ``m`` gathers its children
    from arbitrary arena rows (``child_ids``), pulls its pre-gathered
    external row (``ext_rows[m]`` — already eagerly projected for
    GateSpec cells), and scatters its state to its own arena row
    ``out_ids[m]`` instead of a contiguous level block.  Per-request
    level offsets are therefore pure data resolved host-side — the
    compiled program never changes as requests come and go (the Cavs
    property, extended across requests).

    With ``spec`` the row math routes through the fused frontier
    megastep (``kops.frontier_megastep``) and ``buf`` is in the row
    layout ``[N, 1, S]`` (``megastep.as_rows`` — the caller keeps it
    across its tick scan); without it the op-by-op gather → apply →
    scatter on ``[N, S]``.  Both legs compute bit-identical rows to
    what :func:`execute` computes for the same vertex on the matching
    leg, which is what lets the engine prove per-request bit-identity
    against solo scoring.

    Pad lanes: ``node_mask`` 0, ``child_ids`` at the buffer sentinel,
    ``out_ids`` out of range (unique; the scatter drops them).
    """
    if spec is not None:
        return kops.frontier_megastep(spec.kind, buf, child_ids, child_mask,
                                      ext_rows, node_mask, out_ids,
                                      spec.weights(params))
    M, A = child_ids.shape
    S = buf.shape[1]
    ch = jnp.take(buf, child_ids.reshape(-1), axis=0).reshape(M, A, S)
    io = VertexIO(child_states=ch, child_mask=child_mask.astype(buf.dtype),
                  external=ext_rows, node_mask=node_mask.astype(buf.dtype))
    out = fn.apply(params, io)
    state = (out.state * io.node_mask[:, None]).astype(buf.dtype)
    return kops.scatter_rows(buf, out_ids, state)


# ---------------------------------------------------------------------------
# Readouts (lazy `push`: external consumers read the buffer after the scan)
# ---------------------------------------------------------------------------

def readout_roots(buf: Array, sched: DeviceSchedule) -> Array:
    """``[K, S]`` root states (e.g. tree classification heads)."""
    return jnp.take(buf, sched.root_slots, axis=0)


def readout_nodes(buf: Array, sched: DeviceSchedule) -> Array:
    """``[K, N, S]`` per-node states in original node order (e.g. LM
    per-position hidden states); padded nodes read the zero sentinel."""
    K, N = sched.slot_of.shape
    out = jnp.take(buf, sched.slot_of.reshape(-1), axis=0).reshape(K, N, -1)
    return out * sched.node_valid[..., None]


# ---------------------------------------------------------------------------
# Serial reference policy (the dynamic-declaration baseline)
# ---------------------------------------------------------------------------

def execute_serial(fn: VertexFunction, params: Params,
                   graphs: Sequence[InputGraph],
                   inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """Per-vertex, per-sample execution — the DyNet-style baseline the
    paper compares against (no cross-sample batching, one kernel per
    vertex).  Returns, per sample, a ``[num_nodes, S]`` state matrix.

    Used for correctness oracles and for the Fig. 8 serial-vs-batched
    benchmarks.
    """
    results = []
    A = max(max(g.max_arity for g in graphs), 1,
            getattr(fn, "arity", 1))     # fixed-arity cells (e.g. Tree-FC)
    S = fn.state_dim
    for g, x in zip(graphs, inputs):
        lvl = g.levels()
        states = np.zeros((g.num_nodes, S), np.float32)
        x = np.asarray(x, np.float32)
        for v in np.argsort(lvl, kind="stable"):
            ch = g.children[v]
            cs = np.zeros((A, S), np.float32)
            cm = np.zeros((A,), np.float32)
            for a, c in enumerate(ch):
                cs[a] = states[c]
                cm[a] = 1.0
            er = g.ext_row[v]
            ext = x[er] if er >= 0 else np.zeros(x.shape[1], np.float32)
            ext = jnp.asarray(ext)
            if has_eager_projection(fn):
                # Serial baseline still needs apply()'s expected layout:
                # project this single vertex's pull (one tiny kernel per
                # vertex — exactly the inefficiency the paper measures).
                ext = fn.project_inputs(params, ext[None])[0]
            out = apply_unbatched(fn, params, jnp.asarray(cs), jnp.asarray(cm),
                                  ext)
            states[v] = np.asarray(out.state)
        results.append(states)
    return results
