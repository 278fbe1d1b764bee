"""Fused level-megastep: one Pallas launch per batching task.

The scheduler's op-by-op path realizes one batching task ``V_t`` as
three XLA ops — ``jnp.take`` (gather), ``fn.apply`` (cell), and
``dynamic_update_slice`` (scatter) — so every level round-trips the
``[M, A, S]`` gathered child states and the ``[M, 4H]`` gate tensor
through HBM.  The megastep fuses the whole task:

  (a) **gather** — the node-state buffer is the kernel's (aliased)
      input, left in HBM; scalar-prefetched ``child_ids`` and
      ``ext_ids`` address one DMA per child row and per pulled ext row
      HBM→VMEM (zero gather arithmetic in the vector units);
  (b) **cell** — the recurrent matmuls run on the MXU against
      VMEM-resident weights, the ext-proj rows (hoisted ``W·x``, §3.5)
      arrive with the children, and the gate nonlinearities + state
      update stay in VMEM — the ``[·,4H]`` gates never exist in HBM;
  (c) **scatter** — task ``t`` owns the contiguous buffer block
      ``[t·M, (t+1)·M)`` (§3.3), so the result is a plain block write,
      and ``input_output_aliases`` pins the output to the input buffer:
      the ``lax.scan`` carries ONE buffer in place, no per-level copy.

Reads and writes never overlap (children live at levels ``< t``), which
is what makes the in-place alias sound.

Supported gate kinds (see ``core.vertex.GateSpec``):

  - ``"lstm"``     — arity-1 LSTM, state ``[c|h]``, weights ``(wh, b)``;
  - ``"treelstm"`` — N-ary child-sum Tree-LSTM (paper Fig. 4), state
    ``[c|h]``, weights ``(ui, uf, uo, uu, b)``: the per-child forget
    gates and the sums ``Σ h_k``, ``Σ f_k·c_k`` run over the ``A``
    child tiles;
  - ``"gru"``      — arity-1 GRU, state ``h``, weights ``(wh, b)``
    (3 gate lanes ``z|r|n``; the reset gate multiplies the recurrent
    candidate term *before* the tanh, so the kernel cannot fold the
    recurrence into one pre-activation add the way the LSTM does);
  - ``"treefc"``   — the Tree-FC benchmark cell (paper §5): one FC
    layer over the *concatenated* child states, weights ``(wc, b)``
    with ``wc`` of shape ``[A*H, H]``, accumulated as
    ``Σ h_k @ wc[k*H:(k+1)*H]`` — the concat never materializes.

Chip layout.  Every buffer a megastep gathers from or writes to is held
in the **row layout** ``[R, 1, S]`` (:func:`as_rows`): the leading axis
indexes rows, so one vertex's row is one DMA at any row index.  XLA
gives this shape the compact ``T(1,128)`` HBM tiling — the same bytes
as ``[R, S]``, no padding.  The plain ``[R, S]`` shape is tiled
``(8, 128)`` on the TPU, and Mosaic refuses a one-row block or DMA of
it ("slice shape along dimension 0 must be aligned to tiling (8)").
Callers keep the row layout across a whole ``lax.scan`` — reshaping
per level would relayout the buffer on every launch.

Each grid step handles a block of ``bm`` slots (:func:`block_rows`,
the largest divisor of ``M`` up to 128): it starts one DMA per child
row and per pulled ext row into VMEM, waits for them, runs the gate
math on ``[bm, ·]`` tiles, and writes the block back with ONE DMA to
rows ``[offset + m0, offset + m0 + bm)``.  Child ``a`` of every slot
lands in its own ``[bm, 1, S]`` scratch, so the cell math sees the
children as ``A`` separate ``[bm, S]`` tiles.

Padded blocks.  Bucketed schedules pad levels and widths, and most
blocks of a padded level hold no vertex.  The scheduler appends each
level's live extent to the ext-id scalar operand (:func:`live_ids`:
one flag per block, then the count of real edges); a block whose flag
is 0 starts no DMA, runs no math and writes nothing, so its rows keep
the zeros the scan's buffer starts with — what the masked write gave.
A caller that passes the plain ``[M]`` ext ids (the serving frontier,
hand-built levels) runs every block.

VMEM budget.  Weights are single-buffered (their block never changes);
per grid step VMEM holds the ``A`` child tiles ``[bm, 1, S]``, the ext
rows ``[bm, 1, G]``, the state block and the gate temporaries.  The
scoped VMEM the v5e compiler reports at the paper widths (float32,
H=512) and the widest levels ``chip_smoke.py`` packs: Tree-LSTM
(``M=1888``, ``bm=118``) 9.2 MiB forward / 8.5 MiB backward; LSTM
(``M=64``) 7.1 / 6.7 MiB; GRU 2.3 / 5.5 MiB; Tree-FC (``M=4096``,
``bm=128``) 2.6 / 2.8 MiB — all inside the 16 MiB scoped default.
``tests/test_tpu_compile.py`` compiles each of them for a described
v5e.

The backward half lives here too: :func:`level_bwd` /
:func:`level_param_grads` are the analytic reverse of one megastep —
``∂gather = scatter-add`` (§3.4) for the state chain, plus the pieces
the scheduler's lazy pass batches into ONE flat param-gradient
evaluation over all ``T·M`` slots (§3.5).  Activations are recomputed
from the node buffer (the forward saves nothing else), so the fused
path doubles as a rematerialization policy.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


# ---------------------------------------------------------------------------
# Row layout and one-row DMAs
# ---------------------------------------------------------------------------

def as_rows(x: Array) -> Array:
    """``[R, S]`` → the row layout ``[R, 1, S]`` that the megastep
    kernels gather from and write to (see the module docstring)."""
    return x.reshape(x.shape[0], 1, x.shape[-1])


def from_rows(x: Array) -> Array:
    """Row layout ``[R, 1, S]`` → ``[R, S]``."""
    return x.reshape(x.shape[0], x.shape[-1])


#: Most slots one grid step handles (the MXU is 128 rows tall on v5e).
MAX_BLOCK_ROWS = 128


def block_rows(m: int) -> int:
    """Slots per grid step: the largest divisor of ``m`` up to
    :data:`MAX_BLOCK_ROWS`.  Bucketed widths are multiples of 8, so the
    paper configurations get blocks of at least 8."""
    return max(d for d in range(1, min(m, MAX_BLOCK_ROWS) + 1) if m % d == 0)


def start_row_copies(src, dst, sem, n: int, row_of) -> None:
    """Start ``n`` one-row DMAs ``src[row_of(r)] → dst[r]`` on ``sem``
    (``src``/``dst`` in the row layout)."""
    @pl.loop(0, n)
    def _start(r):
        pltpu.make_async_copy(src.at[row_of(r)], dst.at[r], sem).start()


def wait_row_copies(src, dst, sem, n: int) -> None:
    """Wait for ``n`` equal-sized row DMAs started on ``sem``."""
    @pl.loop(0, n)
    def _wait(r):
        pltpu.make_async_copy(src.at[0], dst.at[r], sem).wait()


def copy_now(src, dst, sem) -> None:
    """One DMA, started and waited for."""
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()


# ---------------------------------------------------------------------------
# Forward cell math on [bm, ·] tiles (children as A separate tiles)
# ---------------------------------------------------------------------------

def _dot(x: Array, w: Array) -> Array:
    return jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _lstm_cell(children, ext, weights):
    wh, b = weights
    H = wh.shape[0]
    prev = children[0]
    c_prev, h_prev = prev[:, :H], prev[:, H:]
    gates = ext + _dot(h_prev, wh) + b
    i = jax.nn.sigmoid(gates[:, :H])
    f = jax.nn.sigmoid(gates[:, H: 2 * H] + 1.0)
    o = jax.nn.sigmoid(gates[:, 2 * H: 3 * H])
    u = jnp.tanh(gates[:, 3 * H:])
    c = f * c_prev + i * u
    return jnp.concatenate([c, o * jnp.tanh(c)], axis=-1)


def _treelstm_cell(children, ext, weights):
    ui, uf, uo, uu, b = weights
    H = ui.shape[0]
    # The bias joins the pulled rows at full width: Mosaic cannot
    # broadcast a [1, H] bias slice taken at a lane offset.
    xb = ext + b
    # Per-child forget gate against h_k (Fig. 4 L9-11).  Absent children
    # gathered the zero sentinel, so f_k·c_k contributes exactly 0 and
    # h_k adds 0 to the child-sum — no mask arithmetic needed.
    h_sum = jnp.zeros_like(ext[:, :H])
    cf = jnp.zeros_like(h_sum)
    for child in children:
        c_k, h_k = child[:, :H], child[:, H:]
        f_k = jax.nn.sigmoid(xb[:, H: 2 * H] + _dot(h_k, uf))
        cf = cf + f_k * c_k
        h_sum = h_sum + h_k
    i = jax.nn.sigmoid(xb[:, :H] + _dot(h_sum, ui))
    o = jax.nn.sigmoid(xb[:, 2 * H: 3 * H] + _dot(h_sum, uo))
    u = jnp.tanh(xb[:, 3 * H:] + _dot(h_sum, uu))
    c = i * u + cf
    return jnp.concatenate([c, o * jnp.tanh(c)], axis=-1)


def _gru_cell(children, ext, weights):
    # The reset gate multiplies the recurrent candidate term BEFORE the
    # tanh, so the recurrence cannot fold into one pre-activation add.
    wh, b = weights
    H = wh.shape[0]
    h_prev = children[0]
    rec = _dot(h_prev, wh) + b
    z = jax.nn.sigmoid(ext[:, :H] + rec[:, :H])
    r = jax.nn.sigmoid(ext[:, H: 2 * H] + rec[:, H: 2 * H])
    n = jnp.tanh(ext[:, 2 * H:] + r * rec[:, 2 * H:])
    return (1.0 - z) * n + z * h_prev


def _treefc_cell(children, ext, weights):
    # Child a's slice of the concat-FC: h_k @ wc[a*H:(a+1)*H] — the
    # concatenated child vector never materializes.
    wc, b = weights
    H = wc.shape[1]
    acc = jnp.zeros_like(ext)
    for a, h_k in enumerate(children):
        acc = acc + _dot(h_k, wc[a * H:(a + 1) * H])
    return jnp.tanh(acc + ext + b)


_CELLS = {"lstm": _lstm_cell, "treelstm": _treelstm_cell,
          "gru": _gru_cell, "treefc": _treefc_cell}


def resident(w: Array) -> pl.BlockSpec:
    """A whole weight matrix held in VMEM for the kernel's lifetime:
    one buffer, since its block never changes."""
    return pl.BlockSpec(w.shape, lambda *_: (0,) * w.ndim,
                        pipeline_mode=pl.Buffered(1))


def gate_weights(weights: Tuple[Array, ...]) -> Tuple[Array, ...]:
    """GateSpec weights as kernel operands: biases ``[G]`` → ``[1, G]``."""
    return tuple(w if w.ndim == 2 else w[None, :] for w in weights)


# ---------------------------------------------------------------------------
# Forward megastep kernel
# ---------------------------------------------------------------------------

def _megastep_kernel(cids_ref, eids_ref, off_ref, buf_ref, ext_ref, nm_ref,
                     *rest, kind: str, A: int, bm: int, nw: int, M: int,
                     gated: bool):
    w_refs = rest[:nw]
    out_ref, chd, exv, st, sem = rest[nw:]
    i = pl.program_id(0)
    m0 = i * bm
    S = st.shape[-1]

    def block():
        # (a) gather: one DMA per child row and per pulled ext row.
        for a in range(A):
            start_row_copies(buf_ref, chd.at[a], sem.at[0], bm,
                             lambda r, a=a: cids_ref[(m0 + r) * A + a])
        start_row_copies(ext_ref, exv, sem.at[1], bm,
                         lambda r: eids_ref[m0 + r])
        for a in range(A):
            wait_row_copies(buf_ref, chd.at[a], sem.at[0], bm)
        wait_row_copies(ext_ref, exv, sem.at[1], bm)
        # (b) cell: gate math on [bm, ·] tiles against resident weights.
        children = [chd[a].reshape(bm, S).astype(jnp.float32)
                    for a in range(A)]
        ext = exv[...].reshape(bm, exv.shape[-1]).astype(jnp.float32)
        state = _CELLS[kind](children, ext, tuple(w[...].astype(jnp.float32)
                                                  for w in w_refs))
        st[...] = (state.reshape(bm, 1, S) * nm_ref[...]).astype(st.dtype)
        # (c) scatter: the block's rows are contiguous — one DMA.
        copy_now(st, out_ref.at[pl.ds(off_ref[0] + m0, bm)], sem.at[2])

    if gated:
        # A block with no real vertex starts no DMA and writes nothing:
        # its rows keep the zeros they were allocated with.
        pl.when(eids_ref[M + i] != 0)(block)
    else:
        block()


def block_live(node_mask):
    """``[T, M]`` node mask → ``[T, M // block_rows(M)]`` booleans: true
    where a grid block of the megastep kernels holds a real vertex.
    Takes a numpy or a jax array."""
    T, M = node_mask.shape
    bm = block_rows(M)
    return (node_mask.reshape(T, M // bm, bm) > 0).any(axis=-1)


def live_ids(ext_ids: Array, node_mask: Array, child_ids: Array,
             sentinel: int) -> Array:
    """Each level's ext ids with its live extent appended, so that the
    megastep kernels skip what holds no vertex: ``[T, M]`` → ``[T, M +
    nb + 1]`` int32, the ``M`` ext ids, then :func:`block_live`'s ``nb``
    flags, then the level's count of child ids other than ``sentinel``
    (its real edges: the sorted-run walk of the backward stops there).
    Computed once for the whole schedule, outside the level scans."""
    T = ext_ids.shape[0]
    edges = jnp.sum(child_ids.reshape(T, -1) != sentinel, axis=1,
                    keepdims=True)
    return jnp.concatenate([ext_ids.astype(jnp.int32),
                            block_live(node_mask).astype(jnp.int32),
                            edges.astype(jnp.int32)], axis=1)


def level_extent(M: int, ext_ids: Array) -> Tuple[int, bool]:
    """``(nb, gated)`` of a level of width ``M``: its grid blocks, and
    whether ``ext_ids`` carries :func:`live_ids`'s flags (length ``M +
    nb + 1``) or is the plain ``[M]`` vector."""
    nb = M // block_rows(M)
    n = ext_ids.shape[0]
    if n not in (M, M + nb + 1):
        raise ValueError(f"ext_ids must hold {M} ids, or {M + nb + 1} with "
                         f"the live flags of live_ids; got {n}")
    return nb, n > M


def megastep(kind: str, buf: Array, child_ids: Array, ext_ids: Array,
             node_mask: Array, offset: Array, ext: Array,
             weights: Tuple[Array, ...], *, interpret: bool = False,
             name: str = "megastep_fwd") -> Array:
    """One fused batching task of gate kind ``kind``, in place.

    ``buf``: ``[T*M+1, 1, S]`` node-state buffer in the row layout
    (aliased: the output IS this buffer with rows ``[offset,
    offset+M)`` replaced); ``child_ids``: ``[M, A]`` buffer rows
    (absent children point at the zero sentinel); ``ext_ids``: ``[M]``
    rows of ``ext`` (``[E, 1, G]``, row layout); ``offset``: scalar
    ``t*M``; ``weights``: the cell's ``GateSpec`` weights.  The ids
    are prefetched flat into SMEM (a 2-D SMEM array pads its last dim
    to 128 words).  ``name`` is the kernel's stable name (the frontier
    leg passes its own).

    ``ext_ids`` may instead be a level's row of :func:`live_ids`: a
    block whose flag is 0 is then skipped — no DMA, no math, no write —
    so its rows keep what ``buf`` held, which must be zeros (the masked
    write would give them).  Without the flags every block runs.
    """
    if kind not in _CELLS:
        raise ValueError(f"unknown megastep gate kind: {kind!r}")
    M, A = child_ids.shape
    S = buf.shape[-1]
    if kind == "treefc" and weights[0].shape[0] != A * weights[0].shape[1]:
        raise ValueError(f"treefc weight expects A*H={A}*"
                         f"{weights[0].shape[1]} rows, "
                         f"got {weights[0].shape[0]}")
    bm = block_rows(M)
    nb, gated = level_extent(M, ext_ids)
    if gated:
        # A dead block keeps block 0's mask: no refetch between them.
        def nm_block(i, c, e, o):
            return jnp.where(e[M + i] != 0, i, 0), 0, 0
    else:
        def nm_block(i, *_):
            return i, 0, 0
    ws = gate_weights(weights)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb,),
        in_specs=[hbm, hbm, pl.BlockSpec((bm, 1, 1), nm_block)]
        + [resident(w) for w in ws],
        out_specs=hbm,
        scratch_shapes=[pltpu.VMEM((A, bm, 1, S), buf.dtype),     # children
                        pltpu.VMEM((bm, 1, ext.shape[-1]), ext.dtype),
                        pltpu.VMEM((bm, 1, S), buf.dtype),        # states
                        pltpu.SemaphoreType.DMA((3,))],
    )
    return pl.pallas_call(
        functools.partial(_megastep_kernel, kind=kind, A=A, bm=bm,
                          nw=len(ws), M=M, gated=gated),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        input_output_aliases={3: 0},     # buf (first tensor operand) → out
        interpret=interpret,
        name=name,
    )(child_ids.reshape(-1).astype(jnp.int32), ext_ids.astype(jnp.int32),
      jnp.reshape(offset, (1,)).astype(jnp.int32), buf, ext,
      (node_mask > 0).astype(buf.dtype).reshape(M, 1, 1), *ws)


# ---------------------------------------------------------------------------
# Analytic backward of one megastep — the SHARED gate-math helpers.
#
# These are plain shape-polymorphic jnp over per-child tiles, so the same
# code runs in three places: (a) the scheduler's flat lazy
# parameter-gradient pass (batched over all T*M slots), (b) the jnp
# oracle reverse sweep (``ops.bwd_megastep`` off-pallas), and (c) INSIDE
# the fused backward Pallas kernel (``level_megastep_bwd.bwd_megastep``),
# where they trace on [bm, S] VMEM tiles.  Keep them kernel-safe: the
# children arrive as a list of A ``[N, S]`` tiles (a stacked
# ``[N, A, S]`` value has no layout Mosaic can lower), masks as A
# ``[N, 1]`` columns or plain scalars, biases as ``[G]`` or ``[1, G]``;
# no ``jnp.take``, no data-dependent shapes.
# ---------------------------------------------------------------------------

def _sum(xs):
    return functools.reduce(jnp.add, xs)


def _lstm_bwd(g_state, children, ext_rows, child_mask, weights):
    wh, b = weights
    H = wh.shape[0]
    prev = children[0].astype(jnp.float32)
    c_prev, h_prev = prev[:, :H], prev[:, H:]
    gates = ext_rows.astype(jnp.float32) + h_prev @ wh.astype(jnp.float32) \
        + b.astype(jnp.float32)
    i = jax.nn.sigmoid(gates[:, :H])
    f = jax.nn.sigmoid(gates[:, H: 2 * H] + 1.0)
    o = jax.nn.sigmoid(gates[:, 2 * H: 3 * H])
    u = jnp.tanh(gates[:, 3 * H:])
    c = f * c_prev + i * u
    tc = jnp.tanh(c)
    g_c, g_h = g_state[:, :H], g_state[:, H:]
    g_o = g_h * tc
    gc = g_c + g_h * o * (1.0 - tc * tc)
    d_gates = jnp.concatenate([
        gc * u * i * (1.0 - i),
        gc * c_prev * f * (1.0 - f),
        g_o * o * (1.0 - o),
        gc * i * (1.0 - u * u),
    ], axis=-1)
    g_child = jnp.concatenate([gc * f, d_gates @ wh.astype(jnp.float32).T],
                              axis=-1) * child_mask[0]
    return [g_child], d_gates, (h_prev,)


def _treelstm_bwd(g_state, children, ext_rows, child_mask, weights):
    ui, uf, uo, uu, b = [w.astype(jnp.float32) for w in weights]
    H = ui.shape[0]
    cs = [ch.astype(jnp.float32) * mk for ch, mk in zip(children, child_mask)]
    c_k = [x[:, :H] for x in cs]
    h_k = [x[:, H:] for x in cs]
    h_sum = _sum(h_k)
    # Bias added at full width before the split (see _treelstm_cell).
    xi, xf, xo, xu = jnp.split(ext_rows.astype(jnp.float32) + b, 4, axis=-1)
    i = jax.nn.sigmoid(xi + h_sum @ ui)
    f = [jax.nn.sigmoid(xf + h @ uf) for h in h_k]
    o = jax.nn.sigmoid(xo + h_sum @ uo)
    u = jnp.tanh(xu + h_sum @ uu)
    c = i * u + _sum([fk * ck * mk for fk, ck, mk in zip(f, c_k, child_mask)])
    tc = jnp.tanh(c)
    g_c, g_h = g_state[:, :H], g_state[:, H:]
    g_o = g_h * tc
    gc = g_c + g_h * o * (1.0 - tc * tc)
    d_i = gc * u * i * (1.0 - i)
    d_u = gc * i * (1.0 - u * u)
    d_o = g_o * o * (1.0 - o)
    d_f = [(gc * ck * mk) * fk * (1.0 - fk)
           for fk, ck, mk in zip(f, c_k, child_mask)]
    d_gates = jnp.concatenate([d_i, _sum(d_f), d_o, d_u], axis=-1)
    g_h_sum = d_i @ ui.T + d_o @ uo.T + d_u @ uu.T
    g_child = [jnp.concatenate([gc * fk, g_h_sum + dfk @ uf.T], axis=-1) * mk
               for fk, dfk, mk in zip(f, d_f, child_mask)]
    return g_child, d_gates, (d_i, d_f, d_o, d_u, h_sum, h_k)


def _gru_bwd(g_state, children, ext_rows, child_mask, weights):
    wh, b = weights
    H = wh.shape[0]
    h_prev = children[0].astype(jnp.float32)                 # [N, H]
    rec = h_prev @ wh.astype(jnp.float32) + b.astype(jnp.float32)
    ext_rows = ext_rows.astype(jnp.float32)
    z = jax.nn.sigmoid(ext_rows[:, :H] + rec[:, :H])
    r = jax.nn.sigmoid(ext_rows[:, H: 2 * H] + rec[:, H: 2 * H])
    hn = rec[:, 2 * H:]
    n = jnp.tanh(ext_rows[:, 2 * H:] + r * hn)
    g_h = g_state.astype(jnp.float32)
    d_n = g_h * (1.0 - z) * (1.0 - n * n)
    d_z = g_h * (h_prev - n) * z * (1.0 - z)
    d_r = d_n * hn * r * (1.0 - r)
    # Pulled-row cotangent: x lanes enter the pre-activations additively.
    d_gates = jnp.concatenate([d_z, d_r, d_n], axis=-1)
    # Recurrent-matmul cotangent: the n lane is gated by r.
    d_rec = jnp.concatenate([d_z, d_r, d_n * r], axis=-1)
    g_h_prev = g_h * z + d_rec @ wh.astype(jnp.float32).T
    return [g_h_prev * child_mask[0]], d_gates, (h_prev, d_rec)


def _treefc_bwd(g_state, children, ext_rows, child_mask, weights):
    wc, b = weights
    H = wc.shape[1]
    h_k = [ch.astype(jnp.float32) * mk for ch, mk in zip(children, child_mask)]
    pre = (jnp.concatenate(h_k, axis=-1) @ wc.astype(jnp.float32)
           + ext_rows.astype(jnp.float32) + b.astype(jnp.float32))
    hy = jnp.tanh(pre)
    d_pre = g_state.astype(jnp.float32) * (1.0 - hy * hy)    # [N, H]
    g_cat = d_pre @ wc.astype(jnp.float32).T                 # [N, A*H]
    g_child = [g_cat[:, a * H:(a + 1) * H] * mk
               for a, mk in enumerate(child_mask)]
    return g_child, d_pre, (h_k,)


_BWD = {"lstm": _lstm_bwd, "treelstm": _treelstm_bwd,
        "gru": _gru_bwd, "treefc": _treefc_bwd}


def level_bwd_tiles(kind: str, g_state: Array, children, ext_rows: Array,
                    child_mask, weights: Tuple[Array, ...]):
    """:func:`level_bwd` on per-child tiles: ``children`` is A
    ``[N, S]`` arrays and ``child_mask`` A ``[N, 1]`` columns (or
    scalars).  Returns ``(g_children, d_gates, aux)`` with
    ``g_children`` a list of A ``[N, S]`` cotangents — the form the
    fused backward kernel traces on its VMEM tiles."""
    fn = _BWD.get(kind)
    if fn is None:
        raise ValueError(f"unknown megastep gate kind: {kind!r}")
    return fn(g_state, children, ext_rows, child_mask, weights)


def level_bwd(kind: str, g_state: Array, child: Array, ext_rows: Array,
              child_mask: Array, weights: Tuple[Array, ...]
              ) -> Tuple[Array, Array, Tuple[Array, ...]]:
    """Reverse one megastep analytically (activations recomputed from
    the gathered child rows — the remat policy).

    ``g_state``: ``[N, S]`` node-masked state cotangent; ``child``:
    ``[N, A, S]`` gathered child rows; ``ext_rows``: ``[N, 4H]``.

    Returns ``(g_child, d_gates, aux)``: ``g_child`` ``[N, A, S]`` is
    the child-mask-masked cotangent to scatter-ADD into the buffer
    (∂gather = scatter-add, §3.4); ``d_gates`` ``[N, 4H]`` is the
    pulled-row cotangent (∂pull = push); ``aux`` feeds
    :func:`level_param_grads`.
    """
    A = child.shape[1]
    g_children, d_gates, aux = level_bwd_tiles(
        kind, g_state, [child[:, a] for a in range(A)], ext_rows,
        [child_mask[:, a, None] for a in range(A)], weights)
    # Per-child aux lists (Tree-LSTM's d_f/h_k, Tree-FC's h_k) stack to
    # the [N, A, H] form level_param_grads batches over.
    aux = tuple(jnp.stack(x, axis=1) if isinstance(x, list) else x
                for x in aux)
    return jnp.stack(g_children, axis=1), d_gates, aux


def level_param_grads(kind: str, d_gates: Array, aux: Tuple[Array, ...],
                      weights: Tuple[Array, ...]) -> Tuple[Array, ...]:
    """Weight gradients from ONE flat batched pass over all slots
    (paper §3.5 lazy batching: the parameter-gradient operators run
    once over every row of the batch, not once per task).  Output order matches
    ``GateSpec.weight_names``.
    """
    if kind == "lstm":
        (h_prev,) = aux
        wh, _ = weights
        return (h_prev.T @ d_gates).astype(wh.dtype), \
            jnp.sum(d_gates, axis=0)
    if kind == "treelstm":
        d_i, d_f, d_o, d_u, h_sum, h_k = aux
        N, A, H = h_k.shape
        return (h_sum.T @ d_i,
                h_k.reshape(N * A, H).T @ d_f.reshape(N * A, H),
                h_sum.T @ d_o,
                h_sum.T @ d_u,
                jnp.concatenate([jnp.sum(d_i, axis=0),
                                 jnp.sum(d_f, axis=(0, 1)),
                                 jnp.sum(d_o, axis=0),
                                 jnp.sum(d_u, axis=0)]))
    if kind == "gru":
        h_prev, d_rec = aux
        wh, _ = weights
        return (h_prev.T @ d_rec).astype(wh.dtype), \
            jnp.sum(d_rec, axis=0)
    if kind == "treefc":
        (h_k,) = aux
        wc, _ = weights
        N, A, H = h_k.shape
        return (h_k.reshape(N, A * H).T @ d_gates).astype(wc.dtype), \
            jnp.sum(d_gates, axis=0)
    raise ValueError(f"unknown megastep gate kind: {kind!r}")


# ---------------------------------------------------------------------------
# Roofline accounting (HBM traffic per batching task)
# ---------------------------------------------------------------------------

def level_traffic_bytes(kind: str, M: int, A: int, S: int, H: int,
                        fused: bool, itemsize: int = 4) -> int:
    """Modeled HBM bytes moved by ONE batching task's forward.

    Unfused (gather → F → scatter as separate XLA ops), per level:
    the gather writes+rereads ``[M, A, S]``, the ext pull writes+rereads
    the ``[M, G]`` gate lanes (``G`` = 4H LSTM-family, 3H GRU, H
    Tree-FC), the dot roots the fusion so the ``[M, G]`` gate tensor
    round-trips, and the state is written then re-read by the
    ``dynamic_update_slice``.  Fused: child rows and ext rows are read
    ONCE (HBM→VMEM) and the state block is written once — every
    intermediate lives in VMEM/registers.  Weight traffic is identical
    (resident either way under scan) and excluded.
    """
    g = {"lstm": 4, "treelstm": 4, "gru": 3, "treefc": 1}[kind] * H
    read_children = M * A * S
    read_ext = M * g
    write_state = M * S
    if fused:
        return (read_children + read_ext + write_state) * itemsize
    gather_rt = 2 * read_children          # materialize + re-read
    ext_rt = 2 * read_ext                  # pulled rows materialize + re-read
    gates_rt = 2 * M * g                   # dot output round-trips
    dus_rt = 2 * write_state               # state tensor + buffer update
    return (read_children + read_ext + gather_rt + ext_rt + gates_rt
            + dus_rt) * itemsize


def level_bwd_traffic_bytes(kind: str, M: int, A: int, S: int, H: int,
                            fused: bool, itemsize: int = 4) -> int:
    """Modeled HBM bytes moved by ONE batching task's reverse step.

    Unfused (the jnp ``level_bwd`` sandwiched between launches): the
    recompute re-gathers the ``[M, A, S]`` child rows (materialize +
    re-read), the pulled ``[M, G]`` ext rows and the recomputed gate
    tensor round-trip, the ``[M, G]`` gate cotangents round-trip, the
    ``[M, A, S]`` child cotangents materialize and are re-read by the
    scatter-add, whose destination rows are read-modified-written.
    Fused (``level_megastep_bwd.bwd_megastep``): child rows, ext rows
    and the ``[M, S]`` state cotangent are read ONCE HBM→VMEM, every
    recomputed gate and every cotangent lives in VMEM scratch, and only
    the touched destination rows (≤ ``M·A``, sorted-run discipline) are
    read + written.  Weight traffic is identical (resident either way
    under scan) and excluded.
    """
    g = {"lstm": 4, "treelstm": 4, "gru": 3, "treefc": 1}[kind] * H
    read_children = M * A * S              # recompute gather (remat)
    read_ext = M * g
    read_gstate = M * S
    dst_rmw = 2 * M * A * S                # scatter-add rows read + write
    if fused:
        return (read_children + read_ext + read_gstate + dst_rmw) * itemsize
    gather_rt = 2 * read_children          # take materializes + cell re-reads
    ext_rt = 2 * read_ext
    gates_rt = 2 * M * g                   # recomputed pre-activations
    dgates_rt = 2 * M * g                  # gate cotangents round-trip
    gchild_rt = 2 * M * A * S              # child cotangents materialize + re-read
    gstate_rt = 2 * read_gstate            # slice materializes + re-read
    return (read_children + read_ext + gather_rt + ext_rt + gates_rt
            + dgates_rt + gchild_rt + gstate_rt + dst_rmw) * itemsize
