"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` layer).

These are the semantic ground truth: simplest correct code, no tiling,
no VMEM reasoning.  Kernel tests sweep shapes/dtypes and assert
``allclose`` against these.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Fused RNN cells (Cavs kernel fusion, §3.5)
# ---------------------------------------------------------------------------

def lstm_gates(gates: jax.Array, c_prev: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """gates ``[M, 4H]`` (i|f|o|u pre-activations), c_prev ``[M, H]``."""
    i, f, o, u = jnp.split(gates, 4, axis=-1)
    i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f + 1.0), jax.nn.sigmoid(o)
    c = f * c_prev + i * jnp.tanh(u)
    return c, o * jnp.tanh(c)


def treelstm_gates(i_pre: jax.Array, f_pre: jax.Array, o_pre: jax.Array,
                   u_pre: jax.Array, c_k: jax.Array,
                   child_mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Child-sum Tree-LSTM gate math (paper Fig. 4 L7-17).

    ``i_pre/o_pre/u_pre``: ``[M, H]``; ``f_pre/c_k``: ``[M, A, H]``;
    ``child_mask``: ``[M, A]``.
    """
    i = jax.nn.sigmoid(i_pre)
    f = jax.nn.sigmoid(f_pre)
    o = jax.nn.sigmoid(o_pre)
    u = jnp.tanh(u_pre)
    c = i * u + jnp.sum(f * c_k * child_mask[..., None], axis=1)
    return c, o * jnp.tanh(c)


# ---------------------------------------------------------------------------
# The four Cavs primitives (gather/scatter memcpy kernels, §4 Backend)
# ---------------------------------------------------------------------------

def gather_rows(src: jax.Array, idx: jax.Array) -> jax.Array:
    """``out[i] = src[idx[i]]`` — Cavs ``gather``/``pull`` memcpy."""
    return jnp.take(src, idx, axis=0)


def scatter_rows(dst: jax.Array, idx: jax.Array, rows: jax.Array) -> jax.Array:
    """``dst[idx[i]] = rows[i]`` (unique indices) — Cavs ``scatter``/``push``."""
    return dst.at[idx].set(rows, mode="drop", unique_indices=True)


def scatter_add_rows(dst: jax.Array, idx: jax.Array,
                     rows: jax.Array) -> jax.Array:
    """``dst[idx[i]] += rows[i]`` (duplicates accumulate) — the
    transpose of ``gather_rows``: ∂gather = scatter-add (Cavs §3.4).
    Oracle for ``kernels/level_megastep_bwd.scatter_add_rows``."""
    return dst.at[idx].add(rows, mode="drop", unique_indices=False,
                           indices_are_sorted=False)


# ---------------------------------------------------------------------------
# Attention (GQA / SWA / causal / cross) — transformer hot-spot
# ---------------------------------------------------------------------------

def mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
        causal: bool = True, window: Optional[int] = None,
        scale: Optional[float] = None) -> jax.Array:
    """Full-materialization attention oracle.

    ``q``: ``[B, Hq, Sq, D]``; ``k``/``v``: ``[B, Hkv, Sk, D]`` with
    ``Hq % Hkv == 0`` (GQA).  ``window``: sliding-window width (SWA) —
    position i attends to ``[i-window+1, i]``.  ``causal=False`` with
    ``Sq != Sk`` is cross-attention.
    """
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kk).astype(jnp.float32) * scale
    Sk = k.shape[2]
    if causal:
        # Align the ends: query i ~ key position i + (Sk - Sq).
        qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)
        kpos = jnp.arange(Sk)[None, :]
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w.astype(q.dtype), vv)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     kv_len: Optional[jax.Array] = None,
                     window: Optional[int] = None) -> jax.Array:
    """Single-token decode attention over a KV cache.

    ``q``: ``[B, Hq, D]``; ``k``/``v``: ``[B, Hkv, S, D]``; ``kv_len``:
    ``[B]`` number of valid cache rows (defaults to full).
    """
    B, Hq, D = q.shape
    S = k.shape[2]
    out = mha(q[:, :, None, :], k, v, causal=False)
    if kv_len is None and window is None:
        return out[:, :, 0, :]
    # With a length mask we must redo the softmax masking.
    Hkv = k.shape[1]
    g = Hq // Hkv
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    logits = jnp.einsum("bhd,bhkd->bhk", q, kk).astype(jnp.float32) * (D ** -0.5)
    pos = jnp.arange(S)[None, :]
    valid = jnp.ones((B, S), bool) if kv_len is None else pos < kv_len[:, None]
    if window is not None:
        last = (jnp.full((B,), S, jnp.int32) if kv_len is None else kv_len)
        valid &= pos >= (last[:, None] - window)
    logits = jnp.where(valid[:, None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", w.astype(q.dtype), vv)


# ---------------------------------------------------------------------------
# Mamba-2 SSD (state-space duality) — sequential-recurrence oracle
# ---------------------------------------------------------------------------

def ssd_reference(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                  C: jax.Array, D: Optional[jax.Array] = None,
                  initial_state: Optional[jax.Array] = None,
                  ) -> Tuple[jax.Array, jax.Array]:
    """Exact sequential SSM recurrence (ground truth for the chunked/
    Pallas SSD paths).

    Shapes (single group): ``x``: ``[Bt, L, H, P]``; ``dt``: ``[Bt, L, H]``;
    ``A``: ``[H]`` (negative log-decay rates); ``B``/``C``: ``[Bt, L, N]``;
    ``D``: ``[H]`` skip.  Returns ``(y [Bt,L,H,P], state [Bt,H,P,N])``.

    Recurrence per head: ``S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t ⊗ B_t``,
    ``y_t = S_t @ C_t (+ D * x_t)``.
    """
    Bt, L, H, P = x.shape
    N = B.shape[-1]
    s0 = (jnp.zeros((Bt, H, P, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(s, inp):
        xt, dtt, Bt_, Ct_ = inp          # [Bt,H,P], [Bt,H], [Bt,N], [Bt,N]
        decay = jnp.exp(dtt * A[None, :])[:, :, None, None]       # [Bt,H,1,1]
        upd = (dtt[:, :, None, None] * xt[..., None]
               * Bt_[:, None, None, :])                            # [Bt,H,P,N]
        s = decay * s + upd
        y = jnp.einsum("bhpn,bn->bhp", s, Ct_)
        return s, y

    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(B, 1, 0).astype(jnp.float32),
          jnp.moveaxis(C, 1, 0).astype(jnp.float32))
    state, ys = jax.lax.scan(step, s0, xs)
    y = jnp.moveaxis(ys, 0, 1)                                     # [Bt,L,H,P]
    if D is not None:
        y = y + D[None, None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), state


def _segsum(z: jax.Array) -> jax.Array:
    """Lower-triangular pairwise segment sums: out[..., i, j] = sum_{j<k<=i} z_k."""
    L = z.shape[-1]
    cs = jnp.cumsum(z, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool), 0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, D: Optional[jax.Array] = None,
                chunk: int = 16,
                initial_state: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD (Dao & Gu 2024, Alg. 1): quadratic *within* chunks,
    linear recurrence *across* chunk states.  This is the jnp rendering of
    what the Pallas kernel tiles; also serves as the sub-quadratic
    long-context path.
    """
    Bt, L, H, P = x.shape
    assert L % chunk == 0, "sequence length must be divisible by chunk"
    nc = L // chunk
    N = B.shape[-1]
    f32 = jnp.float32

    xc = x.reshape(Bt, nc, chunk, H, P).astype(f32)
    dtc = dt.reshape(Bt, nc, chunk, H).astype(f32)
    Bc = B.reshape(Bt, nc, chunk, N).astype(f32)
    Cc = C.reshape(Bt, nc, chunk, N).astype(f32)

    da = dtc * A[None, None, None, :]                 # [Bt,nc,Q,H]
    da = jnp.moveaxis(da, -1, 2)                      # [Bt,nc,H,Q]
    seg = _segsum(da)                                 # [Bt,nc,H,Q,Q]
    Ldec = jnp.exp(seg)

    # Intra-chunk (diagonal block): y = (C B^T ∘ L) · (dt x)
    G = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)         # [Bt,nc,Q,Q]
    M = G[:, :, None] * Ldec                          # [Bt,nc,H,Q,Q]
    y_diag = jnp.einsum("bchij,bcjh,bcjhp->bcihp", M, dtc, xc)

    # Chunk-final states: decayed sum of B-weighted inputs.
    decay_to_end = jnp.exp(jnp.cumsum(da[..., ::-1], axis=-1)[..., ::-1] - da)
    # states [Bt,nc,H,P,N]
    states = jnp.einsum("bchj,bcjh,bcjhp,bcjn->bchpn", decay_to_end, dtc, xc, Bc)

    # Inter-chunk recurrence over chunk states.
    chunk_decay = jnp.exp(jnp.sum(da, axis=-1))       # [Bt,nc,H]
    s0 = (jnp.zeros((Bt, H, P, N), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(s, inp):
        st, dec = inp
        s_new = dec[:, :, None, None] * s + st
        return s_new, s                                # emit state *entering* chunk

    (s_fin, entering) = jax.lax.scan(
        step, s0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)           # [Bt,nc,H,P,N]

    # Inter-chunk contribution: y += C_t · (decay(0→t) * S_entering)
    decay_from_start = jnp.exp(jnp.cumsum(da, axis=-1))          # [Bt,nc,H,Q]
    y_off = jnp.einsum("bcin,bchpn,bchi->bcihp", Cc, entering, decay_from_start)

    y = (y_diag + y_off).reshape(Bt, L, H, P)
    if D is not None:
        y = y + D[None, None, :, None] * x.astype(f32)
    return y.astype(x.dtype), s_fin


def ssd_decode_step(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                    C: jax.Array, D: Optional[jax.Array],
                    state: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One-token SSM update: ``x``: ``[Bt,H,P]``, ``dt``: ``[Bt,H]``,
    ``B/C``: ``[Bt,N]``, ``state``: ``[Bt,H,P,N]``."""
    decay = jnp.exp(dt * A[None, :])[:, :, None, None]
    s = decay * state + (dt[:, :, None, None] * x[..., None] * B[:, None, None, :])
    y = jnp.einsum("bhpn,bn->bhp", s, C)
    if D is not None:
        y = y + D[None, :, None] * x
    return y.astype(x.dtype), s


def megastep_cell_state(kind: str, child: jax.Array, rows: jax.Array,
                        child_mask: jax.Array,
                        weights: Tuple[jax.Array, ...]) -> jax.Array:
    """The megastep cell math alone: gathered child rows ``[M, A, S]``
    plus pulled (eagerly projected) rows ``[M, G]`` → state ``[M, S]``
    (before node masking).  Shared by the forward oracle below and —
    via ``jax.vjp`` — by the per-kind backward oracles, so the analytic
    ``level_megastep.level_bwd`` and the fused backward kernel are
    both tested against plain autodiff of this naive forward.
    """
    M, A = child.shape[:2]
    if kind == "lstm":
        wh, b = weights
        H = wh.shape[0]
        prev = child[:, 0, :]
        gates = rows + prev[:, H:] @ wh + b
        c, h = lstm_gates(gates, prev[:, :H])
        return jnp.concatenate([c, h], axis=-1)
    if kind == "treelstm":
        ui, uf, uo, uu, b = weights
        H = ui.shape[0]
        mk = child_mask.astype(child.dtype)[..., None]
        cs = child * mk
        c_k, h_k = cs[..., :H], cs[..., H:]
        h_sum = jnp.sum(h_k, axis=1)
        xi, xf, xo, xu = jnp.split(rows, 4, axis=-1)
        bi, bf, bo, bu = jnp.split(b, 4)
        # Per-child recurrence as a flattened [M*A, H] matmul: the
        # batched einsum lowers ~2.5x slower on XLA CPU (1.06 vs
        # 0.48 ms at M=460, A=2, H=64).
        rec_f = (h_k.reshape(M * A, H) @ uf).reshape(M, A, H)
        c, h = treelstm_gates(
            xi + h_sum @ ui + bi,
            xf[:, None, :] + rec_f + bf,
            xo + h_sum @ uo + bo,
            xu + h_sum @ uu + bu,
            c_k, child_mask.astype(child.dtype))
        return jnp.concatenate([c, h], axis=-1)
    if kind == "gru":
        wh, b = weights
        H = wh.shape[0]
        h_prev = child[:, 0, :]
        rec = h_prev @ wh + b
        z = jax.nn.sigmoid(rows[:, :H] + rec[:, :H])
        r = jax.nn.sigmoid(rows[:, H: 2 * H] + rec[:, H: 2 * H])
        n = jnp.tanh(rows[:, 2 * H:] + r * rec[:, 2 * H:])
        return (1.0 - z) * n + z * h_prev
    if kind == "treefc":
        wc, b = weights
        mk = child_mask.astype(child.dtype)[..., None]
        cs = (child * mk).reshape(M, -1)                 # [M, A*H] concat
        return jnp.tanh(cs @ wc + rows + b)
    raise ValueError(f"unknown megastep gate kind: {kind!r}")


def level_megastep(kind: str, buf: jax.Array, child_ids: jax.Array,
                   child_mask: jax.Array, ext_ids: jax.Array,
                   node_mask: jax.Array, offset: jax.Array, ext: jax.Array,
                   weights: Tuple[jax.Array, ...]) -> jax.Array:
    """Oracle for ``kernels/level_megastep.py``: one batching task as
    gather (``jnp.take``) → cell math → contiguous block scatter
    (``dynamic_update_slice``), returning the updated buffer.

    Semantically identical to the Pallas megastep; this is also the
    portable forward the scheduler's fused path lowers to off-TPU.
    """
    M, A = child_ids.shape
    S = buf.shape[1]
    child = jnp.take(buf, child_ids.reshape(-1), axis=0).reshape(M, A, S)
    rows = jnp.take(ext, ext_ids, axis=0)
    nm = node_mask.astype(buf.dtype)[:, None]
    state = megastep_cell_state(kind, child, rows,
                                child_mask.astype(buf.dtype), weights)
    return jax.lax.dynamic_update_slice(
        buf, (state * nm).astype(buf.dtype), (offset, 0))


def frontier_megastep(kind: str, buf: jax.Array, child_ids: jax.Array,
                      child_mask: jax.Array, rows: jax.Array,
                      node_mask: jax.Array, out_ids: jax.Array,
                      weights: Tuple[jax.Array, ...]) -> jax.Array:
    """Oracle for the continuous-serving UNION-frontier batching task.

    Like :func:`level_megastep` but the frontier mixes vertices of many
    in-flight graphs at different depths, so destinations are arbitrary
    per-row buffer indices ``out_ids`` (each request's rows live at its
    own arena offset) instead of a contiguous block, and the pulled
    rows arrive pre-gathered as ``rows`` ``[M, G]`` (the engine
    assembles them host-side from per-request external matrices — there
    is no single ``[R+1, X]`` matrix spanning the frontier).

    ``out_ids`` must be unique; pad lanes carry out-of-range ids (the
    scatter drops them) and ``node_mask`` 0.  The row math is exactly
    :func:`megastep_cell_state`, which is what makes frontier execution
    bit-identical per row to the aligned level scan.
    """
    M, A = child_ids.shape
    S = buf.shape[1]
    child = jnp.take(buf, child_ids.reshape(-1), axis=0).reshape(M, A, S)
    nm = node_mask.astype(buf.dtype)[:, None]
    state = megastep_cell_state(kind, child, rows,
                                child_mask.astype(buf.dtype), weights)
    return scatter_rows(buf, out_ids, (state * nm).astype(buf.dtype))


def level_bwd(kind: str, g_state: jax.Array, child: jax.Array,
              rows: jax.Array, child_mask: jax.Array,
              weights: Tuple[jax.Array, ...]
              ) -> Tuple[jax.Array, jax.Array, Tuple[jax.Array, ...]]:
    """Per-kind backward ORACLE: plain ``jax.vjp`` through the naive
    cell forward — no hand-derived math whatsoever.  Ground truth for
    the analytic ``level_megastep.level_bwd``/``level_param_grads`` and
    (through them) the fused backward kernel.

    Returns ``(g_child, d_rows, w_grads)``: the child-mask-masked
    ``[M, A, S]`` cotangent to scatter-add, the ``[M, G]`` pulled-row
    cotangent, and the weight cotangents in ``weights`` order.
    """
    def f(child, rows, weights):
        return megastep_cell_state(kind, child, rows, child_mask, weights)

    _, vjp = jax.vjp(f, child, rows, tuple(weights))
    g_child, d_rows, w_grads = vjp(g_state)
    # The LSTM/GRU forwards rely on sentinel zeros instead of mask
    # arithmetic, so their raw vjp leaves masked child rows nonzero;
    # the sweep must push exact zeros at the sentinel (cf. the analytic
    # backward's masking).
    return g_child * child_mask[..., None], d_rows, w_grads


def bwd_megastep(kind: str, g: jax.Array, buf: jax.Array,
                 child_ids: jax.Array, child_mask: jax.Array,
                 ext_ids: jax.Array, node_mask: jax.Array,
                 offset: jax.Array, ext: jax.Array,
                 weights: Tuple[jax.Array, ...]) -> jax.Array:
    """Oracle for ``kernels/level_megastep_bwd.bwd_megastep``: one
    reverse batching task as slice → autodiff cell backward →
    scatter-add, returning the updated gradient buffer."""
    M, A = child_ids.shape
    S = g.shape[1]
    g_state = jax.lax.dynamic_slice(g, (offset, 0), (M, S)) \
        * node_mask.astype(g.dtype)[:, None]
    child = jnp.take(buf, child_ids.reshape(-1), axis=0).reshape(M, A, S)
    rows = jnp.take(ext, ext_ids, axis=0)
    g_child, _, _ = level_bwd(kind, g_state, child, rows,
                              child_mask.astype(g.dtype), weights)
    return scatter_add_rows(g, child_ids.reshape(-1),
                            g_child.reshape(M * A, S).astype(g.dtype))


def lstm_level_fused(h_prev, c_prev, ext_proj, wh, b):
    """Oracle for kernels/level_step.py: recurrent matmul + LSTM cell."""
    H = h_prev.shape[1]
    gates = ext_proj + h_prev.astype(jnp.float32) @ wh.astype(jnp.float32) + b
    i, f, o, u = jnp.split(gates.astype(jnp.float32), 4, axis=-1)
    i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f + 1.0), jax.nn.sigmoid(o)
    c = f * c_prev.astype(jnp.float32) + i * jnp.tanh(u)
    h = o * jnp.tanh(c)
    return c.astype(h_prev.dtype), h.astype(h_prev.dtype)
