"""Vertex-centric programming interface (Cavs §3.1).

A dynamic neural network is decomposed into a static *vertex function*
``F`` and a dynamic, instance-specific *input graph* ``G``.  The vertex
function is declared once, symbolically, against four message-passing
primitives:

  - ``gather(k)``  — read the state of the k-th child vertex,
  - ``scatter(s)`` — write this vertex's state for its parents,
  - ``pull()``     — read inputs external to ``(F, G)``,
  - ``push(o)``    — write outputs for external consumers.

In this JAX adaptation the four primitives are mediated by two pytrees:

  - :class:`VertexIO` is what a (batched) application of ``F`` *sees*:
    the gathered child states, the pulled external rows and validity
    masks.  ``gather``/``pull`` are methods on it.
  - :class:`VertexOutput` is what the application *produces*: the
    scattered state and the (optional) pushed output.

``F`` itself is a :class:`VertexFunction`: a pure ``apply`` over
parameters plus a ``VertexIO`` batch.  Because every application has the
same static shape, XLA compiles ``F`` exactly once — the paper's
"declared and optimized once" property.
"""

from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Optional, Protocol, Sequence, Tuple,
                    runtime_checkable)

import jax
import jax.numpy as jnp

Params = Any
Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class VertexIO:
    """The batched view one evaluation of ``F`` receives (Cavs Fig. 3).

    All leading dimensions are ``M`` — the number of node slots in the
    current batching task ``V_t`` (padded; see ``node_mask``).
    """

    #: ``[M, A, S]`` gathered child states (``A`` = max arity).  Rows of
    #: absent children are the zero sentinel and masked off below.
    child_states: Array
    #: ``[M, A]`` float {0,1}: 1 where the child exists.
    child_mask: Array
    #: ``[M, X]`` pulled external rows (embeddings, frontend features, or
    #: eager-hoisted input projections — see core/fusion.py).
    external: Array
    #: ``[M]`` float {0,1}: 1 where the slot holds a real vertex.
    node_mask: Array

    # -- the paper's four primitives, reading side ---------------------
    def gather(self, child_idx: int) -> Array:
        """Cavs ``gather(child_idx)``: state of the child at that index.

        Returns ``[M, S]`` (zeros where the child does not exist).
        """
        return self.child_states[:, child_idx, :] * self.child_mask[:, child_idx, None]

    def gather_sum(self) -> Array:
        """Child-sum convenience: sum of all existing children, ``[M, S]``."""
        return jnp.sum(self.child_states * self.child_mask[..., None], axis=1)

    def pull(self) -> Array:
        """Cavs ``pull()``: the external input row for each slot, ``[M, X]``."""
        return self.external

    @property
    def num_slots(self) -> int:
        return self.child_states.shape[0]

    @property
    def arity(self) -> int:
        return self.child_states.shape[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class VertexOutput:
    """What one evaluation of ``F`` produces.

    ``state`` is the *scattered* value — it is written into the node-state
    buffer for parent vertices to ``gather``.  ``push`` is the value made
    visible to consumers *external* to ``(F, G)`` (e.g. the loss head);
    it is collected lazily (Cavs lazy batching) after all tasks finish.
    """

    #: ``[M, S]`` scatter value (for Tree-LSTM: ``concat([c, h])``, as in
    #: the paper's Fig. 4 line 18).
    state: Array
    #: ``[M, O]`` pushed output, or ``None`` if this F pushes nothing.
    push: Optional[Array] = None


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ArenaIO:
    """What one frontier tick of a vertex whose state stays in the
    arena sees (chains only): each lane is one vertex that updates its
    chain's state row in place, plus the key/value rows of every
    earlier vertex of its chain.

    A vertex function that declares ``apply_arena(params, arena, io)``
    gets this instead of :class:`VertexIO`: its state row (every
    layer's recurrent state, megabytes a vertex) is never gathered into
    an ``[M, S]`` batch.  ``arena`` is the engine's pytree of pools
    (``init_arena``); the function returns it updated.
    """

    #: ``[M]`` int32 the chain's state row: the lane reads its
    #: predecessor's state there and writes its own over it (the
    #: arena's spare row on pad lanes).  The lane's push goes to the
    #: same row of the push buffer.
    rows: Array
    #: ``[M]`` int32: 0 pad lane, 1 first vertex of its chain (no
    #: predecessor: its row is not read), 2 a vertex with a predecessor.
    flags: Array
    #: ``[M]`` int32 the vertex's raw input (a token id).
    tokens: Array
    #: ``[M]`` int32 index of the vertex along its chain.
    position: Array
    #: ``[M, B]`` int32 the chain's key/value blocks, in order.
    kv_blocks: Array


@dataclasses.dataclass(frozen=True)
class GateSpec:
    """A cell's declaration that its gate math is *megastep-fusable*.

    The fused level-megastep kernel (``kernels/level_megastep.py``) can
    only run cells whose vertex function factors as

        gates = pulled_ext_proj + recurrent(child_states) ; state = cell(gates)

    with a known ``kind``.  A cell that declares a ``GateSpec`` (via a
    ``gate_spec()`` method) opts into the scheduler's fused path: one
    Pallas launch per batching task instead of gather → apply → scatter
    as three separate XLA ops.  ``weight_names`` are the keys of the
    params dict the kernel consumes (the eager ``wx`` projection stays
    outside — it is hoisted, §3.5); the analytic backward writes its
    gradients back under the same keys.
    """

    #: Gate-math kind understood by ``kernels/level_megastep.py``:
    #: "lstm" (arity-1, state ``[c|h]``), "treelstm" (N-ary child-sum,
    #: state ``[c|h]``; paper Fig. 4), "gru" (arity-1, state ``h``) or
    #: "treefc" (fixed-arity concat-FC benchmark cell, state ``h``).
    kind: str
    hidden: int
    weight_names: Tuple[str, ...]
    #: Fixed gather arity required by the kernel, or ``None`` when the
    #: gate math is arity-agnostic.  "treefc" concatenates the children
    #: against a ``[arity*H, H]`` weight, so the packed schedule's
    #: ``A`` must match exactly; the scheduler falls back to the
    #: op-by-op path (under "auto") when it does not.
    arity: Optional[int] = None

    _STATE_MULT = {"lstm": 2, "treelstm": 2, "gru": 1, "treefc": 1}
    _GATE_MULT = {"lstm": 4, "treelstm": 4, "gru": 3, "treefc": 1}

    @property
    def state_dim(self) -> int:
        """Width of the scattered state row (``[c|h]`` doubles it)."""
        return self._STATE_MULT[self.kind] * self.hidden

    @property
    def gate_dim(self) -> int:
        """Width of the pulled (eagerly projected) external row — the
        number of gate lanes times ``hidden``."""
        return self._GATE_MULT[self.kind] * self.hidden

    def weights(self, params: Params) -> Tuple[Array, ...]:
        return tuple(params[n] for n in self.weight_names)

    def inject_grads(self, params: Params, grads: Sequence[Array]) -> Params:
        """Zero cotangent tree for ``params`` with the megastep weight
        gradients filled in (the hoisted ``wx`` grads are added by the
        caller via the projection VJP)."""
        out = jax.tree.map(jnp.zeros_like, params)
        for name, g in zip(self.weight_names, grads):
            out[name] = g
        return out


def get_gate_spec(fn: Any) -> Optional[GateSpec]:
    """The cell's fusable gate declaration, or ``None`` (unfused path)."""
    getter = getattr(fn, "gate_spec", None)
    return getter() if callable(getter) else None


@runtime_checkable
class VertexFunction(Protocol):
    """The static vertex function ``F`` (Cavs §3.1).

    Implementations are pure: ``apply(params, io)`` must be traceable by
    JAX with no side effects.  ``state_dim`` is the width of the
    scattered state; ``ext_dim`` the width of the pulled external rows
    *as seen by apply* (after optional eager projection).
    """

    state_dim: int
    ext_dim: int
    arity: int

    def init(self, rng: Array) -> Params: ...

    def apply(self, params: Params, io: VertexIO) -> VertexOutput: ...

    # -- optional hooks -------------------------------------------------
    # project_inputs(params, raw_external) -> projected_external
    #   Declares the *eager* prefix of F (Cavs Def. 1): ops that depend on
    #   no other vertex.  When present, the scheduler hoists it out of the
    #   sequential region and evaluates it over ALL nodes in one batch
    #   (the streaming/eager optimization, §3.5).


@dataclasses.dataclass(frozen=True)
class LambdaVertex:
    """Wrap plain functions as a :class:`VertexFunction`."""

    state_dim: int
    ext_dim: int
    arity: int
    init_fn: Callable[[Array], Params]
    apply_fn: Callable[[Params, VertexIO], VertexOutput]
    project_fn: Optional[Callable[[Params, Array], Array]] = None

    def init(self, rng: Array) -> Params:
        return self.init_fn(rng)

    def apply(self, params: Params, io: VertexIO) -> VertexOutput:
        return self.apply_fn(params, io)

    def project_inputs(self, params: Params, raw: Array) -> Array:
        if self.project_fn is None:
            raise AttributeError("no eager projection declared")
        return self.project_fn(params, raw)

    @property
    def has_projection(self) -> bool:
        return self.project_fn is not None


def has_eager_projection(fn: Any) -> bool:
    """True if ``fn`` declares an eager input projection (streaming hook)."""
    if isinstance(fn, LambdaVertex):
        return fn.has_projection
    return callable(getattr(fn, "project_inputs", None))


def apply_unbatched(fn: VertexFunction, params: Params,
                    child_states: Array, child_mask: Array,
                    external: Array) -> VertexOutput:
    """Evaluate ``F`` on a single vertex (M=1) — the serial reference path.

    ``child_states``: ``[A, S]``; ``child_mask``: ``[A]``; ``external``: ``[X]``.
    """
    io = VertexIO(
        child_states=child_states[None],
        child_mask=child_mask[None].astype(child_states.dtype),
        external=external[None],
        node_mask=jnp.ones((1,), child_states.dtype),
    )
    out = fn.apply(params, io)
    return VertexOutput(
        state=out.state[0],
        push=None if out.push is None else out.push[0],
    )
