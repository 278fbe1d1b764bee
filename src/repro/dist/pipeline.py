"""Pipeline parallelism over the ``pod`` axis (GPipe schedule, SPMD).

``gpipe_spmd`` runs a stack of identical stages as a shard_map over a
one-axis mesh: stage ``i`` holds slice ``i`` of the stacked parameters,
microbatches stream stage-to-stage with ``ppermute``, and the last
stage's outputs are replicated back with a masked ``psum``.  The whole
schedule is a static Python loop of ``n_micro + n_stages - 1`` ticks, so
it lowers to one XLA program and is differentiable end-to-end (the
transpose of ``ppermute`` is the reverse permute — the backward pipeline
comes for free).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble: ``(S-1) / (M + S - 1)`` idle fraction."""
    if n_stages <= 1:
        return 0.0
    return (n_stages - 1) / (n_micro + n_stages - 1)


def gpipe_spmd(stage_fn: Callable[[Any, jax.Array], jax.Array],
               mesh: Mesh,
               loss_fn: Optional[Callable[[jax.Array], jax.Array]] = None):
    """Build ``f(stacked_params, xs)`` running the GPipe schedule.

    ``stacked_params``: pytree whose leaves have leading dim
    ``n_stages``; ``xs``: ``[n_micro, microbatch, ...]``.  Returns the
    ``[n_micro, microbatch, ...]`` outputs of the final stage, or
    ``loss_fn(outputs)`` when a loss is given.
    """
    (axis,) = mesh.axis_names
    n = mesh.shape[axis]

    def run(stacked, xs):
        m = xs.shape[0]

        def body(p_local, xs_full):
            # p_local leaves are [1, ...] — this stage's slice.
            p = jax.tree.map(lambda a: a[0], p_local)
            idx = jax.lax.axis_index(axis)
            perm = [(i, (i + 1) % n) for i in range(n)]
            carry = jnp.zeros_like(xs_full[0])
            outs = jnp.zeros_like(xs_full)
            for t in range(m + n - 1):
                x_in = jnp.where(idx == 0, xs_full[min(t, m - 1)], carry)
                y = stage_fn(p, x_in)
                if t >= n - 1:
                    j = t - (n - 1)
                    outs = outs.at[j].set(jnp.where(idx == n - 1, y, outs[j]))
                carry = jax.lax.ppermute(y, axis, perm)
            # Replicate the last stage's outputs everywhere.
            return jax.lax.psum(
                jnp.where(idx == n - 1, outs, jnp.zeros_like(outs)), axis)

        out = jax.shard_map(body, mesh=mesh,
                            in_specs=(P(axis), P()), out_specs=P(),
                            check_vma=False)(stacked, xs)
        return loss_fn(out) if loss_fn is not None else out

    return run
