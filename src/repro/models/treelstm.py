"""Tree-structured vertex functions: N-ary child-sum Tree-LSTM (paper
Fig. 4) and the Tree-FC benchmark cell (paper §5, from the Fold loom
benchmarks).

The Tree-LSTM follows Tai et al. [50] exactly as transcribed in the
paper's Fig. 4: per-child forget gates against the *individual* child
hidden states, remaining gates against the child-sum.  The scattered
state is ``concat([c, h])`` (Fig. 4 L18).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core.vertex import GateSpec, VertexIO, VertexOutput
from repro.models.layers import dense_init as _dense_init

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TreeLSTMVertex:
    """N-ary child-sum Tree-LSTM (Cavs Fig. 4), arity ``N``.

    State: ``[c | h]`` (width ``2*hidden``); external: token embedding
    rows of width ``input_dim``, eagerly projected to the 4 gate lanes.
    """

    input_dim: int
    hidden: int
    arity: int = 2
    cell_impl: str = "jnp"

    @property
    def state_dim(self) -> int:
        return 2 * self.hidden

    @property
    def ext_dim(self) -> int:
        return 4 * self.hidden

    def init(self, rng) -> Params:
        kx, ki, kf, ko, ku = jax.random.split(rng, 5)
        h = self.hidden
        return {
            # W^(i)|W^(f)|W^(o)|W^(u) stacked: one eager matmul for all gates.
            "wx": _dense_init(kx, self.input_dim, 4 * h),
            "ui": _dense_init(ki, h, h),
            "uf": _dense_init(kf, h, h),
            "uo": _dense_init(ko, h, h),
            "uu": _dense_init(ku, h, h),
            "b": jnp.zeros((4 * h,), jnp.float32),
        }

    def project_inputs(self, params: Params, raw: jax.Array) -> jax.Array:
        """Eager prefix: ``x @ [W_i W_f W_o W_u]`` — Fig. 7's `pull` branch."""
        return raw @ params["wx"]

    def gate_spec(self) -> GateSpec:
        """Fusable-gate declaration: each batching task runs as ONE
        fused megastep launch that walks the ``A`` children on an inner
        grid axis (``kernels/level_megastep.py``)."""
        return GateSpec(kind="treelstm", hidden=self.hidden,
                        weight_names=("ui", "uf", "uo", "uu", "b"))

    def apply(self, params: Params, io: VertexIO) -> VertexOutput:
        h = self.hidden
        xi, xf, xo, xu = jnp.split(io.pull(), 4, axis=-1)
        bi, bf, bo, bu = jnp.split(params["b"], 4)

        # Fig. 4 L2-6: gather children, split into (c_k, h_k), child-sum h.
        M, A = io.num_slots, io.arity
        cs = io.child_states * io.child_mask[..., None]       # [M, A, 2H]
        c_k, h_k = cs[..., :h], cs[..., h:]
        h_sum = jnp.sum(h_k, axis=1)                          # Σ_k h_k
        # Per-child forget recurrence flattened to [M*A, H] @ [H, H]:
        # the batched einsum lowers ~2.5x slower on XLA CPU (1.06 vs
        # 0.48 ms at M=460, A=2, H=64).
        rec_f = (h_k.reshape(M * A, h) @ params["uf"]).reshape(M, A, h)

        if self.cell_impl == "pallas":
            from repro.kernels import ops as kops
            c, hy = kops.treelstm_gates(
                xi + h_sum @ params["ui"] + bi,
                # per-child forget pre-activations [M, A, H]:
                xf[:, None, :] + rec_f + bf,
                xo + h_sum @ params["uo"] + bo,
                xu + h_sum @ params["uu"] + bu,
                c_k, io.child_mask)
        else:
            i = jax.nn.sigmoid(xi + h_sum @ params["ui"] + bi)
            # Fig. 4 L9-11: one forget gate per child against h_k.
            f = jax.nn.sigmoid(xf[:, None, :] + rec_f + bf)
            o = jax.nn.sigmoid(xo + h_sum @ params["uo"] + bo)
            u = jnp.tanh(xu + h_sum @ params["uu"] + bu)
            c = i * u + jnp.sum(f * c_k * io.child_mask[..., None], axis=1)
            hy = o * jnp.tanh(c)
        return VertexOutput(state=jnp.concatenate([c, hy], axis=-1))


@dataclasses.dataclass(frozen=True)
class TreeFCVertex:
    """The Tree-FC benchmark cell (paper §5 'Models'): a single
    fully-connected layer over the concatenated child states, plus the
    leaf embedding path.  Binary trees (arity 2)."""

    input_dim: int
    hidden: int
    arity: int = 2

    @property
    def state_dim(self) -> int:
        return self.hidden

    @property
    def ext_dim(self) -> int:
        return self.hidden

    def init(self, rng) -> Params:
        kx, kc = jax.random.split(rng)
        return {
            "wx": _dense_init(kx, self.input_dim, self.hidden),
            "wc": _dense_init(kc, self.arity * self.hidden, self.hidden),
            "b": jnp.zeros((self.hidden,), jnp.float32),
        }

    def project_inputs(self, params: Params, raw: jax.Array) -> jax.Array:
        return raw @ params["wx"]

    def gate_spec(self) -> GateSpec:
        """Fusable-gate declaration (kind "treefc").  The concat weight
        fixes the gather arity, so the fused path only engages when the
        packed schedule's ``A`` equals ``self.arity`` (the scheduler
        falls back to op-by-op otherwise under ``fusion_mode="auto"``).
        """
        return GateSpec(kind="treefc", hidden=self.hidden,
                        weight_names=("wc", "b"), arity=self.arity)

    def apply(self, params: Params, io: VertexIO) -> VertexOutput:
        M = io.num_slots
        cs = (io.child_states * io.child_mask[..., None]).reshape(M, -1)
        hy = jnp.tanh(cs @ params["wc"] + io.pull() + params["b"])
        return VertexOutput(state=hy)
