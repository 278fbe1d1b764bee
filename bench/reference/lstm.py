"""LSTM over a chain (Hochreiter & Schmidhuber 1997), gate lanes
``i | f | o | u`` with a forget-gate bias of 1 added inside the
sigmoid, state ``[c | h]``.  The first vertex of a chain
reads zeros."""

from __future__ import annotations

import jax
import jax.numpy as jnp

FORGET_BIAS = 1.0


def init(key, cfg: dict) -> dict:
    """Uniform ``±1/sqrt(fan_in)`` matrices, zero bias."""
    h, x = cfg["vertex_args"]["hidden"], cfg["vertex_args"]["input_dim"]
    kx, kh = jax.random.split(key)
    lim_x, lim_h = 1.0 / jnp.sqrt(x), 1.0 / jnp.sqrt(h)
    return {"wx": jax.random.uniform(kx, (x, 4 * h), jnp.float32,
                                     -lim_x, lim_x),
            "wh": jax.random.uniform(kh, (h, 4 * h), jnp.float32,
                                     -lim_h, lim_h),
            "b": jnp.zeros((4 * h,), jnp.float32)}


def cell(p: dict, children, mask, xw, dot):
    h = p["wh"].shape[0]
    prev = children[:, 0] * mask[:, :1].astype(children.dtype)
    c_prev, h_prev = prev[:, :h], prev[:, h:]
    g = xw + dot(h_prev, p["wh"]) + p["b"]
    i = jax.nn.sigmoid(g[:, :h])
    f = jax.nn.sigmoid(g[:, h:2 * h] + FORGET_BIAS)
    o = jax.nn.sigmoid(g[:, 2 * h:3 * h])
    c = f * c_prev + i * jnp.tanh(g[:, 3 * h:])
    return jnp.concatenate([c, o * jnp.tanh(c)], axis=-1)
