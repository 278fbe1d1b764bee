"""Mamba-2 one-token update (conv and state) over a union frontier, in
the arena.

Each lane of a serving tick is one token of one chain.  The chain's
recurrent state lives in one row of the engine's state arena; the
lane reads the state its predecessor left there and writes its own
over it, by a row id that is scalar-prefetched, so the ``[M, S]``
states are never staged through a gathered copy.  Grid ``(M,)``: one
lane a step, the row's layer block streamed HBM→VMEM into an input
buffer, the new block computed into an output buffer and written back
to the same row by the BlockSpec pipeline (the next lane's block is
fetched while this one computes).  In place is safe because the live
lanes of a launch hold distinct rows: a block is fetched before its
lane computes and written after, and no other lane touches it; pad
lanes share the spare row, which nothing reads.

The ``xBC`` lanes of a token (``x``, then ``B`` and ``C`` of ``N``
values each) are handled in *conv rows*: ``[CR, W]`` with ``CR = R + 2``
— ``R`` rows of ``x`` (``W`` lanes, 128 at the published widths), then
``B`` and ``C`` in a row each (``N ≤ W`` lanes, the rest zero).

Layout of one layer's block of a state row, ``[R*N + (K-1)*CRp, W]``
float32:

  * rows ``[0, R*N)`` — the SSM state: tile ``r`` (rows
    ``[r*N, (r+1)*N)``) holds ``state[h, p, n]`` at sublane ``n`` and
    lane ``j`` where ``h*P + p = r*W + j``: the head-major flattening of
    ``x`` runs along the lanes, the state dimension down the sublanes;
  * then the depthwise conv's tail, the last ``K - 1`` raw ``xBC`` rows
    in conv rows, oldest first, each slot padded to ``CRp`` (a multiple
    of 8) rows.

Per token: ``conv = silu(Σ_k tail_k·w_k + new·w_{K-1} + b)``; ``x``, ``B``,
``C`` from it; per tile ``s ← exp(dt·A) ⊙ s + B ⊗ (dt·x)`` and
``y = Σ_n C[n]·s[n, :] + D·x``, all elementwise in float32 (``B`` and
``C`` as columns: one transpose a lane).  ``dt`` comes in per element
of ``x`` (its head's value repeated), already through the softplus.

Flags (scalar-prefetched, a word a lane): 0 — pad lane, nothing
computed (its blocks map to the arena's spare row); 1 — a chain's
first token, the predecessor's state and tail are zero (whatever its
row held before is not read); 2 — a token with a predecessor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def slot_rows(conv_rows: int) -> int:
    """Rows one tail slot takes in a state block (8-row aligned)."""
    return -(-conv_rows // 8) * 8


def _ssm_step_kernel(row_ref, flag_ref, arena_ref, xbc_ref, dt_ref,
                     cw_ref, cb_ref, a_ref, d_ref, y_ref, out_ref, *,
                     R: int, N: int, K: int):
    flag = flag_ref[pl.program_id(0)]
    CR = R + 2
    base, step = R * N, slot_rows(CR)

    def update(has_pred: bool):
        new = xbc_ref[0]                                   # [CR, W]
        tails = [arena_ref[base + k * step: base + k * step + CR, :]
                 for k in range(K - 1)] if has_pred else []
        acc = new * cw_ref[K - 1]
        if tails:
            acc = tails[0] * cw_ref[0]
            for k in range(1, K - 1):
                acc = acc + tails[k] * cw_ref[k]
            acc = acc + new * cw_ref[K - 1]
        acc = acc + cb_ref[...]
        conv = acc * jax.nn.sigmoid(acc)
        for k in range(K - 1):
            nxt = (tails[k + 1] if k + 1 < len(tails) else
                   new if k == K - 2 else jnp.zeros_like(new))
            out_ref[base + k * step: base + k * step + CR, :] = nxt
        x, dt = conv[:R], dt_ref[0]                        # [R, W]
        xdt, dec = dt * x, jnp.exp(dt * a_ref[...])
        dx = d_ref[...] * x
        b_col = jnp.transpose(jnp.broadcast_to(conv[R:R + 1, :N], (N, N)))
        c_col = jnp.transpose(jnp.broadcast_to(conv[R + 1:R + 2, :N],
                                               (N, N)))
        b_col, c_col = b_col[:, 0:1], c_col[:, 0:1]        # [N, 1]
        for r in range(R):
            rows = slice(r * N, (r + 1) * N)
            s = b_col * xdt[r:r + 1, :]                    # [N, W]
            if has_pred:
                s = dec[r:r + 1, :] * arena_ref[rows, :] + s
            out_ref[rows, :] = s
            y_ref[0, r:r + 1, :] = (jnp.sum(c_col * s, axis=0, keepdims=True)
                                    + dx[r:r + 1, :])

    # A first token's predecessor state is zero: what its row held
    # before (a retired chain's state) is never read.
    pl.when(flag == 1)(lambda: update(False))
    pl.when(flag == 2)(lambda: update(True))


def ssm_step(arena: jax.Array, layer: int, rows: jax.Array,
             flags: jax.Array, xbc: jax.Array, dt: jax.Array,
             conv_w: jax.Array, conv_b: jax.Array, a: jax.Array,
             d: jax.Array, *, interpret: bool = False):
    """One token of every lane through Mamba layer ``layer``.

    ``arena``: ``[rows, L, R*N + (K-1)*CRp, W]`` (aliased in place);
    ``rows``/``flags``: ``[M]`` int32, each live lane's own row, read
    and written (pad lanes point at a spare row); ``xbc``: ``[M, CR, W]`` the raw
    new ``xBC`` in conv rows; ``dt``: ``[M, R, W]``; ``conv_w``
    ``[K, CR, W]``, ``conv_b`` ``[CR, W]``; ``a`` (``A``) and ``d``
    (``D``) ``[R, W]``, each head's value repeated over its lanes.
    Returns ``(y [M, R, W], arena)``.  The launch is named ``ssm_step``
    on the device trace.
    """
    M, R, W = dt.shape
    K, CR = conv_w.shape[0], xbc.shape[1]
    blk = arena.shape[2]
    N = (blk - (K - 1) * slot_rows(CR)) // R
    if CR != R + 2 or N > W or blk != R * N + (K - 1) * slot_rows(CR):
        raise ValueError(f"arena block {arena.shape[2:]} does not fit "
                         f"R={R}, conv rows {CR}, K={K}")

    def lane(m, *_):
        return m, 0, 0

    def whole(m, *_):
        return (0,) * 2

    def row_block(m, rows, fl):
        return rows[m], layer, 0, 0

    row_blk = (None, None, blk, W)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M,),
        in_specs=[pl.BlockSpec(row_blk, row_block),
                  pl.BlockSpec((1, CR, W), lane),
                  pl.BlockSpec((1, R, W), lane),
                  pl.BlockSpec((K, CR, W), lambda m, *_: (0, 0, 0)),
                  pl.BlockSpec((CR, W), whole),
                  pl.BlockSpec((R, W), whole),
                  pl.BlockSpec((R, W), whole)],
        out_specs=[pl.BlockSpec((1, R, W), lane),
                   pl.BlockSpec(row_blk, row_block)],
    )
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_ssm_step_kernel, R=R, N=N, K=K),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((M, R, W), f32),
                   jax.ShapeDtypeStruct(arena.shape, arena.dtype)],
        input_output_aliases={2: 1},     # arena (first tensor operand)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name="ssm_step",
    )(rows.astype(jnp.int32), flags.astype(jnp.int32), arena, xbc.astype(f32), dt.astype(f32),
      conv_w.astype(f32), conv_b.astype(f32), a.astype(f32), d.astype(f32))


def ssm_step_ref(arena: jax.Array, layer: int, rows: jax.Array,
                 flags: jax.Array, xbc: jax.Array, dt: jax.Array,
                 conv_w: jax.Array, conv_b: jax.Array, a: jax.Array,
                 d: jax.Array):
    """The same update op by op: gather the lanes' blocks, update,
    scatter them back (pad lanes dropped)."""
    M, R, W = dt.shape
    K, CR = conv_w.shape[0], xbc.shape[1]
    step = slot_rows(CR)
    blk = arena.shape[2]
    N = (blk - (K - 1) * step) // R
    old = arena[rows, layer]                                # [M, blk, W]
    has = (flags > 1)[:, None, None]
    tails = [jnp.where(has, old[:, R * N + k * step: R * N + k * step + CR],
                       0.0) for k in range(K - 1)]
    window = tails + [xbc]
    acc = sum(t * conv_w[k] for k, t in enumerate(window)) + conv_b
    conv = jax.nn.silu(acc)
    x = conv[:, :R]
    bv, cv = conv[:, R, :N], conv[:, R + 1, :N]
    s = old[:, :R * N].reshape(M, R, N, W)
    s = jnp.where(has[..., None], jnp.exp(dt * a)[:, :, None, :] * s, 0.0) \
        + bv[:, None, :, None] * (dt * x)[:, :, None, :]
    y = jnp.sum(cv[:, None, :, None] * s, axis=2) + d * x
    pad = jnp.zeros((M, step - CR, W), arena.dtype)
    new = jnp.concatenate([s.reshape(M, R * N, W)]
                          + [p for t in window[1:] for p in (t, pad)],
                          axis=1)
    dest = jnp.where(flags > 0, rows, arena.shape[0])
    return y, arena.at[dest, layer].set(new.astype(arena.dtype), mode="drop")


def to_conv_rows(v: jax.Array, d_inner: int, N: int, W: int) -> jax.Array:
    """``[..., d_inner + 2N]`` (``x | B | C``) → ``[..., R + 2, W]``."""
    lead = v.shape[:-1]
    pad = [(0, 0)] * len(lead) + [(0, W - N)]
    x = v[..., :d_inner].reshape(lead + (d_inner // W, W))
    b = jnp.pad(v[..., d_inner:d_inner + N], pad)[..., None, :]
    c = jnp.pad(v[..., d_inner + N:], pad)[..., None, :]
    return jnp.concatenate([x, b, c], axis=-2)
