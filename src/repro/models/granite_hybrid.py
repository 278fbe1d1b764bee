"""Granite-4.0-H (IBM's Mamba-2 / attention hybrid) as a vertex function.

One vertex is one token of a chain; ``F`` runs a period of decoder
layers on the token's embedding, in order.  Each layer is
``h += r·mixer(norm(h))`` then ``h += r·mlp(norm(h))`` (``r`` the
residual multiplier), the mixer a Mamba-2 layer or, at the indices
``layer_types`` names, grouped-query softmax attention without
positional encoding.  The layer equations are those of Hugging Face's
``GraniteMoeHybrid`` with no experts.

State: the vertex's state row is every Mamba layer's recurrent state
(an ``[H, P, N]`` SSM state and the conv's last ``K - 1`` raw rows),
kept in the engine's arena in the layout ``kernels/ssm_step.py``
describes and never gathered: a chain holds one row, which each of its
vertices reads and overwrites in place.  The attention layer writes the
token's key and value to the chain's row for this position in a paged
pool, and attends over the rows of positions ``0..t`` of its chain.

The vertex's input is its token id: ``embed`` looks it up and scales
it by the embedding multiplier inside the tick.  The vertex pushes the
final-norm hidden state, and
:meth:`GraniteHybridVertex.logits` turns pushes into full-vocabulary
logits over the logits scaling.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.vertex import ArenaIO
from repro.kernels import ops as kops
from repro.kernels import ssm_step as ks
from repro.models.layers import rmsnorm
from repro.models.mamba import MambaDims, _gated_norm, _split_proj

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class HybridDims:
    hidden: int
    vocab: int
    layer_types: Tuple[str, ...]
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    d_conv: int
    expand: int
    attn_heads: int
    kv_heads: int
    attn_scale: float
    mlp: int
    eps: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float

    @classmethod
    def from_hf(cls, cfg: Dict[str, Any]) -> "HybridDims":
        """From the keys of a Hugging Face ``granitemoehybrid`` config;
        the layers are the first ``num_hidden_layers`` of
        ``layer_types``."""
        if (cfg.get("num_local_experts", 0)
                or cfg.get("mamba_n_groups", 1) != 1):
            raise ValueError("experts and grouped B/C are not supported")
        return cls(
            hidden=cfg["hidden_size"], vocab=cfg["vocab_size"],
            layer_types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
            ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
            d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"],
            expand=cfg["mamba_expand"], attn_heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            attn_scale=cfg["attention_multiplier"],
            mlp=cfg["shared_intermediate_size"], eps=cfg["rms_norm_eps"],
            embedding_multiplier=cfg["embedding_multiplier"],
            residual_multiplier=cfg["residual_multiplier"],
            logits_scaling=cfg["logits_scaling"])

    @property
    def mamba(self) -> MambaDims:
        return MambaDims(d_model=self.hidden, d_state=self.d_state,
                         headdim=self.ssm_head_dim, expand=self.expand,
                         d_conv=self.d_conv)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def head_dim(self) -> int:
        return self.hidden // self.attn_heads

    @property
    def mamba_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "mamba")

    # -- the state row's layout (kernels/ssm_step.py) ---------------------
    @property
    def lanes(self) -> int:
        return min(128, self.d_inner)

    @property
    def ssm_tiles(self) -> int:
        return self.d_inner // self.lanes

    @property
    def tail_rows(self) -> int:
        """Rows of the conv tail: ``K - 1`` slots of ``x | B | C`` in
        conv rows (``kernels/ssm_step.py``)."""
        return (self.d_conv - 1) * ks.slot_rows(self.ssm_tiles + 2)

    @property
    def block_rows(self) -> int:
        return self.ssm_tiles * self.d_state + self.tail_rows

    @property
    def state_floats(self) -> int:
        """Floats of one state row (every Mamba layer's block)."""
        return len(self.mamba_layers) * self.block_rows * self.lanes


class GraniteHybridVertex:
    """The period as a vertex function over arena-resident state, for
    the serving path (``apply_arena``; no op-by-op ``apply``).  Its
    parameters are the pytree ``bench/reference/granite_hybrid.py``'s
    ``init`` builds.

    ``kv_block`` — tokens a block of the key/value pool; ``max_len`` —
    the longest chain, which sizes each lane's block table."""

    arity = 1
    input_dim = 1                     # the token id, as a float

    def __init__(self, dims: HybridDims, *, kv_block: int = 64,
                 max_len: int = 1536):
        if (dims.d_inner % dims.lanes or dims.d_state > dims.lanes
                or dims.hidden % dims.attn_heads):
            raise ValueError("d_inner must fill whole lane rows and "
                             "d_state fit one")
        self.dims = dims
        self.kv_block = kv_block
        self.max_len = max_len
        self.max_blocks = -(-max_len // kv_block)
        self.push_dim = dims.hidden
        self.state_dim = dims.state_floats

    # -- input, arena, readout ------------------------------------------------
    def embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        """Token ids ``[M]`` → embeddings times the embedding
        multiplier ``[M, hidden]``."""
        return params["embed"][tokens] * self.dims.embedding_multiplier

    def init_arena(self, num_rows: int, kv_blocks: int) -> Dict[str, Any]:
        """The engine's pools, each with one spare row past the
        allocatable ones: ``state`` ``[num_rows + 1, L, rows, W]``, the
        key/value pools ``[kv_blocks + 1, kv_block, Hkv·Dh]`` and the
        push buffer ``[num_rows + 1, hidden]``."""
        d = self.dims
        kv = d.kv_heads * d.head_dim
        return {"state": jnp.zeros((num_rows + 1, len(d.mamba_layers),
                                    d.block_rows, d.lanes), jnp.float32),
                "k": jnp.zeros((kv_blocks + 1, self.kv_block, kv),
                               jnp.float32),
                "v": jnp.zeros((kv_blocks + 1, self.kv_block, kv),
                               jnp.float32),
                "push": jnp.zeros((num_rows + 1, d.hidden), jnp.float32)}

    def logits(self, params: Params, pushed: jax.Array) -> jax.Array:
        """Final-norm hidden states ``[K, hidden]`` → ``[K, vocab]``."""
        return (pushed @ params["embed"].T) / self.dims.logits_scaling

    # -- one frontier tick ----------------------------------------------------
    def apply_arena(self, params: Params, arena: Dict[str, Any],
                    io: ArenaIO) -> Dict[str, Any]:
        d = self.dims
        r = d.residual_multiplier
        h = self.embed(params, io.tokens)
        state, k_pool, v_pool = arena["state"], arena["k"], arena["v"]
        m = 0
        for kind, lp in zip(d.layer_types, params["layers"]):
            n = rmsnorm({"scale": lp["norm1"]}, h, d.eps)
            if kind == "mamba":
                out, state = self._mamba(lp, state, m, n, io)
                m += 1
            else:
                out, k_pool, v_pool = self._attention(lp, k_pool, v_pool, n,
                                                      io)
            h = h + r * out
            n = rmsnorm({"scale": lp["norm2"]}, h, d.eps)
            gu = n @ lp["w_mlp_in"]
            h = h + r * ((jax.nn.silu(gu[:, :d.mlp]) * gu[:, d.mlp:])
                         @ lp["w_mlp_out"])
        pushed = rmsnorm({"scale": params["final_norm"]}, h, d.eps)
        # Pad lanes write past the push buffer and are dropped.
        dest = jnp.where(io.flags > 0, io.rows, arena["push"].shape[0])
        return {"state": state, "k": k_pool, "v": v_pool,
                "push": arena["push"].at[dest].set(pushed, mode="drop")}

    def _mamba(self, lp: Params, state: jax.Array, layer: int,
               n: jax.Array, io: ArenaIO):
        d = self.dims
        M = n.shape[0]
        W, R, P, N = d.lanes, d.ssm_tiles, d.ssm_head_dim, d.d_state
        z, xbc, dt_raw = _split_proj(n @ lp["w_in"], d.mamba)
        rows = functools.partial(ks.to_conv_rows, d_inner=d.d_inner, N=N,
                                 W=W)
        dt = jax.nn.softplus(dt_raw + lp["dt_bias"])             # [M, H]
        y, state = kops.ssm_step(
            state, layer, io.rows, io.flags, rows(xbc),
            jnp.repeat(dt, P, axis=1).reshape(M, R, W), rows(lp["conv_w"]),
            rows(lp["conv_b"]),
            jnp.repeat(-jnp.exp(lp["A_log"]), P).reshape(R, W),
            jnp.repeat(lp["D"], P).reshape(R, W))
        y = _gated_norm(y.reshape(M, d.d_inner), z, lp["gnorm"], d.eps)
        return y @ lp["w_out"], state

    def _attention(self, lp: Params, k_pool: jax.Array, v_pool: jax.Array,
                   n: jax.Array, io: ArenaIO):
        d = self.dims
        M = n.shape[0]
        bt = self.kv_block
        q = (n @ lp["wq"]).reshape(M, d.attn_heads, d.head_dim)
        # This token's key and value go to its chain's row for the
        # position; pad lanes write past the pool and are dropped.
        blk = jnp.take_along_axis(io.kv_blocks, (io.position // bt)[:, None],
                                  axis=1)[:, 0]
        blk = jnp.where(io.flags > 0, blk, k_pool.shape[0])
        off = io.position % bt
        k_pool = k_pool.at[blk, off].set(n @ lp["wk"], mode="drop")
        v_pool = v_pool.at[blk, off].set(n @ lp["wv"], mode="drop")
        o = kops.paged_decode_attention(q, k_pool, v_pool, io.kv_blocks,
                                        io.position + 1, scale=d.attn_scale)
        return o.reshape(M, d.hidden) @ lp["wo"], k_pool, v_pool
