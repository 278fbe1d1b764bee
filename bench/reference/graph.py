"""Level-by-level evaluation of a vertex cell over many structures,
and the training steps of the plain reference.

The structures of one call are laid out as one block of vertices; a
``lax.scan`` walks the levels, each gathering the children's states
from the block, applying the cell and writing the level's states back.
Shapes are rounded up to powers of two so that a handful of programs
serve every batch, and so the persistent compilation cache finds them
again in the next run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from generate import levels as struct_levels
from generate import root as struct_root


def _split(x):
    """``x`` as a high and a low bfloat16 part.  The high part is taken
    by masking the low 16 bits: a conversion to bfloat16 and back could
    be folded away by the compiler."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), x.dtype)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _dot3_passes(a, w):
    (ah, al), (wh, wl) = _split(a), _split(w)
    d = lambda u, v: jnp.dot(u, v, preferred_element_type=a.dtype)
    return d(ah, wh) + d(ah, wl) + d(al, wh)


@jax.custom_vjp
def _dot3(a, w):
    return _dot3_passes(a, w)


def _dot3_fwd(a, w):
    return _dot3_passes(a, w), (a, w)


def _dot3_bwd(res, g):
    # Both products of the gradient take three passes as well.
    a, w = res
    a2, g2 = a.reshape(-1, a.shape[-1]), g.reshape(-1, g.shape[-1])
    return _dot3_passes(g, w.T), _dot3_passes(a2.T, g2)


_dot3.defvjp(_dot3_fwd, _dot3_bwd)


def make_dot(precision: str):
    """The matrix product at ``precision``: ``"highest"`` (float32),
    ``"high"`` (three bfloat16 passes: the operands split into a high
    and a low bfloat16 part, the low-by-low product dropped, float32
    accumulation, in the gradient's products too — what a TPU's
    ``Precision.HIGH`` does, written out so that it computes the same on
    any backend) or ``"default"``."""
    if precision == "highest":
        return lambda a, w: jnp.dot(a, w, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        return _dot3
    if precision == "default":
        return jnp.dot
    raise ValueError(f"unknown precision {precision!r}")


def _pow2(n: int, floor: int) -> int:
    return max(floor, 1 << max(0, int(n) - 1).bit_length())


def plan(structs, arity: int) -> dict:
    """Index arrays of ``structs`` laid out as one block of vertices:
    per level the vertices' rows (``ids``, pad lanes point one past the
    block and are dropped), their children's rows (``cids``, absent
    children point at a zero row) and which children exist."""
    sizes = np.array([len(s) for s in structs], np.int64)
    offs = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    lv = np.concatenate([struct_levels(s) for s in structs])
    n = int(sizes.sum())
    L = int(lv.max()) + 1
    counts = np.bincount(lv, minlength=L)
    Lp, Wp, Np = _pow2(L, 8), _pow2(counts.max(), 64), _pow2(n + 1, 256)
    zero_row = Np - 1
    ids = np.full((Lp, Wp), Np, np.int32)
    cids = np.full((Lp, Wp, arity), zero_row, np.int32)
    cmask = np.zeros((Lp, Wp, arity), np.float32)
    flat_children = [[c + o for c in ch] for s, o in zip(structs, offs)
                     for ch in s]
    order = np.argsort(lv, kind="stable")
    fill = np.zeros(L, np.int64)
    for v in order:
        t = lv[v]
        m = fill[t]
        fill[t] += 1
        ids[t, m] = v
        for a, c in enumerate(flat_children[v]):
            cids[t, m, a] = c
            cmask[t, m, a] = 1.0
    roots = (offs + np.array([struct_root(s) for s in structs])).astype(
        np.int32)
    return {"ids": ids, "cids": cids, "cmask": cmask, "roots": roots,
            "rows": Np, "n": n, "offsets": offs, "sizes": sizes}


def block_inputs(inputs, pl: dict) -> np.ndarray:
    """The structures' input rows stacked at their vertices' rows."""
    x = np.zeros((pl["rows"], inputs[0].shape[1]), np.float32)
    x[: pl["n"]] = np.concatenate(inputs)
    return x


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def states(cellmod, p, ids, cids, cmask, x, *, dtype, precision):
    """The state of every vertex, ``[rows, S]``, with values held in
    ``dtype`` and matrix products at ``precision``."""
    dot = make_dot(precision)
    p = _cast(p, dtype)
    xw = dot(x.astype(dtype), p["wx"])
    S = 2 * p["b"].shape[0] // 4
    buf = jnp.zeros((x.shape[0], S), dtype)

    def level(buf, lv):
        lid, lc, lm = lv
        ch = jnp.take(buf, lc, axis=0)
        xl = jnp.take(xw, lid, axis=0, mode="fill", fill_value=0)
        st = cellmod.cell(p, ch, lm, xl, dot).astype(dtype)
        return buf.at[lid].set(st, mode="drop"), None

    buf, _ = jax.lax.scan(level, buf, (ids, cids, cmask))
    return buf


#: The reference proper; a control passes another ``(dtype, precision)``.
EXACT = (jnp.float32, "highest")


@functools.lru_cache(maxsize=None)
def root_states_fn(cellmod, dtype, precision):
    def f(p, ids, cids, cmask, x, roots):
        return jnp.take(states(cellmod, p, ids, cids, cmask, x, dtype=dtype,
                               precision=precision),
                        roots, axis=0).astype(jnp.float32)
    return jax.jit(f)


def root_states(cellmod, p, pl: dict, x: np.ndarray, how=EXACT):
    return root_states_fn(cellmod, *how)(
        p, pl["ids"], pl["cids"], pl["cmask"], x, pl["roots"])


@functools.lru_cache(maxsize=None)
def loss_grad_fn(cellmod, dtype, precision):
    """Mean over the structures of the squared error of the root's
    hidden half against its target, and its gradient."""
    def loss(p, ids, cids, cmask, x, roots, target):
        r = jnp.take(states(cellmod, p, ids, cids, cmask, x, dtype=dtype,
                            precision=precision),
                     roots, axis=0).astype(jnp.float32)
        h = r[:, r.shape[1] // 2:]
        return jnp.mean(jnp.mean((h - target) ** 2, axis=-1))
    return jax.jit(jax.value_and_grad(loss))


#: AdamW's epsilon: the usual value, which the trainer has no setting
#: to change.
EPS = 1e-8


def lr_at(opt: dict, step: int) -> float:
    """The linear warm-up's learning rate at ``step``; the reference
    follows the first steps only, all inside the warm-up."""
    if step >= opt["warmup_steps"]:
        raise ValueError(f"step {step} is past the warm-up")
    return opt["lr"] * (step + 1.0) / opt["warmup_steps"]


def train_steps(cellmod, params, batches, opt: dict, how=EXACT):
    """AdamW over ``batches`` (each ``(plan, x, target)``) from
    ``params``, the gradient clipped to a global norm of
    ``max_grad_norm`` first and decay applied to matrices only.
    Returns each step's loss, the first clipped gradient and the
    parameters' change over all the steps."""
    lg = loss_grad_fn(cellmod, *how)
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    mu = jax.tree.map(jnp.zeros_like, p)
    nu = jax.tree.map(jnp.zeros_like, p)
    b1, b2, wd = opt["b1"], opt["b2"], opt["weight_decay"]
    losses, grad1 = [], None
    for s, (pl, x, target) in enumerate(batches):
        loss, g = lg(p, pl["ids"], pl["cids"], pl["cmask"], x, pl["roots"],
                     target)
        g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
        norm = jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, opt["max_grad_norm"]
                            / jnp.maximum(norm, 1e-12))
        g = jax.tree.map(lambda a: a * scale, g)
        if grad1 is None:
            grad1 = g
        t = s + 1
        lr = lr_at(opt, s)
        mu = jax.tree.map(lambda m, a: b1 * m + (1 - b1) * a, mu, g)
        nu = jax.tree.map(lambda v, a: b2 * v + (1 - b2) * a * a, nu, g)

        def upd(w, m, v):
            d = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + EPS)
            if w.ndim >= 2 and wd:
                d = d + wd * w
            return w - lr * d

        p = jax.tree.map(upd, p, mu, nu)
        losses.append(float(loss))
    delta = jax.tree.map(lambda a, b: a - jnp.asarray(b, jnp.float32),
                         p, params)
    return {"losses": losses, "grad1": grad1, "delta": delta}
