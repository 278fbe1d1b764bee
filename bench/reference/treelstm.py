"""Binary child-sum Tree-LSTM (Tai et al. 2015, as Cavs Fig. 4 writes
it): a forget gate per child against that child's hidden state, the
other gates against the sum of the children's hidden states.

State ``[c | h]``; input gate lanes ``i | f | o | u`` from one
projection ``x @ wx``.  A child that is absent contributes zero to
both sums.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init(key, cfg: dict) -> dict:
    """Uniform ``±1/sqrt(fan_in)`` matrices, zero bias."""
    h, x = cfg["vertex_args"]["hidden"], cfg["vertex_args"]["input_dim"]
    ks = jax.random.split(key, 5)

    def dense(k, n_in, n_out):
        lim = 1.0 / jnp.sqrt(n_in)
        return jax.random.uniform(k, (n_in, n_out), jnp.float32, -lim, lim)

    return {"wx": dense(ks[0], x, 4 * h), "ui": dense(ks[1], h, h),
            "uf": dense(ks[2], h, h), "uo": dense(ks[3], h, h),
            "uu": dense(ks[4], h, h),
            "b": jnp.zeros((4 * h,), jnp.float32)}


def cell(p: dict, children, mask, xw, dot):
    """``children [W, A, 2H]``, ``mask [W, A]``, ``xw [W, 4H]`` (the
    projected inputs) → new states ``[W, 2H]``; ``dot`` is the matrix
    product at the precision asked for."""
    h = p["ui"].shape[0]
    children = children * mask[..., None].astype(children.dtype)
    c_k, h_k = children[..., :h], children[..., h:]
    g = xw + p["b"]
    gi, gf, go, gu = g[:, :h], g[:, h:2 * h], g[:, 2 * h:3 * h], g[:, 3 * h:]
    h_sum = jnp.sum(h_k, axis=1)
    f_k = jax.nn.sigmoid(gf[:, None, :] + dot(h_k, p["uf"]))
    i = jax.nn.sigmoid(gi + dot(h_sum, p["ui"]))
    o = jax.nn.sigmoid(go + dot(h_sum, p["uo"]))
    u = jnp.tanh(gu + dot(h_sum, p["uu"]))
    c = i * u + jnp.sum(f_k * c_k, axis=1)
    return jnp.concatenate([c, o * jnp.tanh(c)], axis=-1)
