"""Single-token decode attention over a KV cache (flash-decode style).

Grid ``(B, Hq, Sk/bk)``: each step streams one KV block HBM→VMEM and
folds it into per-query online-softmax stats.  The valid cache length is
scalar-prefetched (``kv_len[b]``) so ragged caches — continuous batching,
the Cavs Var-LSTM story — mask correctly without host-side repacking.
Sliding windows (SWA) restrict to the last ``window`` cache rows.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _decode_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float,
                   window: Optional[int], block_k: int, num_k_blocks: int):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                      # [1, D]
    k = k_ref[0, 0].astype(jnp.float32)                      # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    kv_len = kvlen_ref[b]
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kpos < kv_len
    if window is not None:
        valid &= kpos >= kv_len - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.max(s, axis=1)[:, None]
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_ref[...] = l_prev * alpha + jnp.broadcast_to(
        jnp.sum(p, axis=1)[:, None], l_prev.shape)
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     kv_len: Optional[jax.Array] = None,
                     window: Optional[int] = None,
                     scale: Optional[float] = None, block_k: int = 256,
                     interpret: bool = False) -> jax.Array:
    """``q``: ``[B, Hq, D]``; ``k``/``v``: ``[B, Hkv, S, D]`` →
    ``[B, Hq, D]``."""
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    group = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bk = min(block_k, _round_up(S, 8))
    Sp = _round_up(S, bk)
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    nk = Sp // bk
    if kv_len is None:
        kv_len = jnp.full((B,), S, jnp.int32)

    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               block_k=bk, num_k_blocks=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, D), lambda b, h, ik, kvl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, ik, kvl, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, ik, kvl, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h, ik, kvl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, D), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q[:, :, None, :], kp, vp)
    return out[:, :, 0, :]


def decode_attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             kv_len: Optional[jax.Array] = None,
                             window: Optional[int] = None,
                             scale: Optional[float] = None,
                             block_k: int = 1024) -> jax.Array:
    """Portable twin of the decode kernel (same blocking, plain jnp)."""
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    group = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bk = min(block_k, S)
    Sp = _round_up(S, bk)
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    nk = Sp // bk
    if kv_len is None:
        kv_len = jnp.full((B,), S, jnp.int32)

    def k_step(st, xs):
        m, l, acc = st
        ik, kb, vb = xs
        kbg = jnp.repeat(kb, group, axis=1)
        vbg = jnp.repeat(vb, group, axis=1)
        s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                       kbg.astype(jnp.float32)) * scale
        kpos = ik * bk + jnp.arange(bk)[None, :]
        valid = (kpos < jnp.minimum(kv_len[:, None], S))
        if window is not None:
            valid &= kpos >= kv_len[:, None] - window
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhk,bhkd->bhd", p, vbg.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    st0 = (jnp.full((B, Hq), NEG_INF, jnp.float32),
           jnp.zeros((B, Hq), jnp.float32),
           jnp.zeros((B, Hq, D), jnp.float32))
    ks = jnp.moveaxis(kp.reshape(B, Hkv, nk, bk, D), 2, 0)
    vs = jnp.moveaxis(vp.reshape(B, Hkv, nk, bk, D), 2, 0)
    (m, l, acc), _ = jax.lax.scan(k_step, st0, (jnp.arange(nk), ks, vs))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l[..., None]).astype(q.dtype)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_table: jax.Array,
                           kv_len: jax.Array, *,
                           scale: Optional[float] = None) -> jax.Array:
    """Single-token GQA attention over a paged cache, plain jnp.

    ``q``: ``[M, Hq, D]``; ``k_pool``/``v_pool``: ``[blocks, bt, Hkv*D]``
    (a row a token, ``bt`` tokens a block); ``block_table``: ``[M, nb]``
    each lane's blocks in order (pad entries point at any block);
    ``kv_len``: ``[M]`` valid tokens a lane.  Positions at or past
    ``kv_len`` get no weight, whatever their rows hold.  Each lane's
    blocks are gathered whole (``nb·bt`` rows), the price of a jnp path;
    a Pallas twin would stream only the blocks below ``kv_len``."""
    M, Hq, D = q.shape
    nb = block_table.shape[1]
    bt = k_pool.shape[1]
    Hkv = k_pool.shape[2] // D
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    valid = jnp.arange(nb * bt)[None, :] < kv_len[:, None]      # [M, T]
    k = k_pool[block_table].reshape(M, nb * bt, Hkv, D)
    v = jnp.where(valid[:, :, None, None],
                  v_pool[block_table].reshape(M, nb * bt, Hkv, D), 0.0)
    qg = q.reshape(M, Hkv, g, D).astype(jnp.float32)
    s = jnp.einsum("mhgd,mthd->mhgt", qg, k.astype(jnp.float32)) * scale
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("mhgt,mthd->mhgd", p, v.astype(jnp.float32))
    return o.reshape(M, Hq, D).astype(q.dtype)
