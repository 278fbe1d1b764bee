"""The fused backward level-megastep + the standalone scatter-ADD.

The megastep reverse sweep propagates state-chain cotangents level by
level (∂gather = scatter-add, §3.4).  This module renders the WHOLE
reverse step as one launch, mirroring the forward megastep:

  :func:`bwd_megastep` — one ``pallas_call`` per reverse level that
    (a) re-gathers the child rows from the residual node buffer via
        scalar-prefetched ``child_ids`` (recompute/remat — the forward
        saved nothing but the buffer),
    (b) runs the analytic cotangent math for the declared gate kind
        (lstm / gru / treelstm / treefc — the SAME shape-polymorphic
        helpers ``level_megastep.level_bwd`` uses, traced here on
        ``[bm, S]`` VMEM tiles), and
    (c) folds the duplicate-safe ∂gather scatter-add into the same
        launch, with the gradient buffer aliased in place.

All buffers are in the row layout ``[R, 1, S]`` (see
``level_megastep``), so every gather and every destination row is one
DMA at any row index.

The grid is ``(M/bm + 1,)``:

  * steps ``[0, M/bm)`` each take a block of ``bm`` slots: DMA in the
    child rows, the pulled ext rows and the block's ``[bm, S]`` state
    cotangent (contiguous), run the cotangent math, and DMA the ``A``
    child-cotangent tiles out to an HBM stash ``[A, M, 1, S]`` (an
    extra output the wrapper drops).  VMEM holds one block, never the
    whole level: at the widest level (Tree-FC, ``M = 4096``) a
    level-resident ``[M·A, S]`` f32 carry alone would be 16 MB;
  * the last step walks the level's contributions in **sorted-run**
    order.  Duplicate destinations (a vertex gathered by several
    parents in one level, multi-parent DAGs Fig. 2d) are adjacent, so
    each destination row is read once, accumulates its whole run in
    VMEM, and is written once.  Contributions aimed at the zero
    sentinel (absent children — exact zeros) are skipped, so a level
    costs DMAs only for its real edges.  The sort is pure schedule
    preprocessing (the schedule is data, §3.2): ``pack_batch``
    precomputes the permutation, the sorted ids and the run heads
    host-side and carries them in ``LevelSchedule.sort_perm`` /
    ``.sorted_child_ids`` / ``.run_head`` — a grad step runs ZERO
    device sorts.  Callers without a packed schedule (hand-built
    levels) may omit them and pay one ``jnp.argsort`` here.

With the live flags of ``level_megastep.live_ids`` the steps of blocks
that hold no vertex do nothing (their stash rows are never read: a
real edge's slot is always real), and the walk stops at the level's
count of real edges: the sentinel is the largest id, so its run is
the sorted tail.

Every DMA of the run walk is waited for before the next starts, and
grid steps run in order, so duplicates are correct by construction
and deterministic; untouched rows are preserved by the alias.

VMEM per launch is one block's tiles plus the single-buffered weights
— 8.5 MiB compiled for Tree-LSTM at ``M=1888`` (see
``level_megastep``), whatever the level width.

:func:`scatter_add_rows` (the standalone memory op, exported as a Cavs
primitive) is the same sorted-run walk over caller-supplied rows.

The jnp oracles (``ref.scatter_add_rows``, ``ref.bwd_megastep``) stay
the interpret-mode and CPU ground truth; ``ops.scatter_add_rows`` /
``ops.bwd_megastep`` dispatch between them.
"""

from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import level_megastep as lm


def sorted_runs(ids: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(perm, sorted_ids, run_head)`` of a flat id vector on device:
    duplicate ids become adjacent and ``run_head`` flags the first
    element of each run (the device twin of ``pack_batch``'s
    host-side ``_sorted_runs``)."""
    ids = ids.astype(jnp.int32)
    perm = jnp.argsort(ids).astype(jnp.int32)
    sids = ids[perm]
    head = jnp.concatenate([jnp.ones((1,), jnp.int32),
                            (sids[1:] != sids[:-1]).astype(jnp.int32)])
    return perm, sids, head


def _sorted_run_add(out_ref, src_row: Callable, sid_ref, perm_ref, head_ref,
                    n: int, live: Callable, acc, row, sem,
                    count=None) -> None:
    """Add ``n`` contribution rows into ``out_ref`` (row layout) in
    sorted-destination order: each run of equal destinations is read
    once, accumulated in VMEM and written once.  ``src_row(p)`` is the
    ref view of contribution ``p``'s row; destinations ``d`` with
    ``live(d)`` false are skipped.  ``count`` (a traced scalar) stops
    the walk after the first ``count`` sorted entries, where the rest
    are known to be skipped."""
    def body(k, carry):
        d = sid_ref[k]

        @pl.when(live(d))
        def _add():
            @pl.when(head_ref[k] == 1)
            def _seed():
                lm.copy_now(out_ref.at[d], acc, sem)

            lm.copy_now(src_row(perm_ref[k]), row, sem)
            acc[...] += row[...]
            last = jnp.logical_or(
                k == n - 1, head_ref[jnp.minimum(k + 1, n - 1)] == 1)

            @pl.when(last)
            def _flush():
                lm.copy_now(acc, out_ref.at[d], sem)

        return carry

    jax.lax.fori_loop(0, n if count is None else count, body, 0)


# ---------------------------------------------------------------------------
# Standalone scatter-add
# ---------------------------------------------------------------------------

def _scatter_add_kernel(sid_ref, perm_ref, head_ref, dst_ref, rows_ref,
                        out_ref, acc, row, sem, *, n: int, R: int):
    del dst_ref  # rides along only for the alias
    _sorted_run_add(out_ref, lambda p: rows_ref.at[p], sid_ref, perm_ref,
                    head_ref, n, lambda d: jnp.logical_and(d >= 0, d < R),
                    acc, row, sem.at[0])


def scatter_add_rows(dst: jax.Array, idx: jax.Array, rows: jax.Array, *,
                     interpret: bool = False) -> jax.Array:
    """``dst``: ``[R, D]``; ``idx``: ``[n]`` int32 (repeats allowed);
    ``rows``: ``[n, D]`` → ``dst`` with ``rows[i]`` added at ``idx[i]``
    (functional; the dst buffer is aliased in place).  Out-of-range
    indices are dropped, as in ``ref.scatter_add_rows``.

    Takes and returns the plain ``[R, D]`` shape, so on the chip each
    call relayouts ``dst`` to and from the row layout; the fused
    backward (:func:`bwd_megastep`) keeps its buffer in the row layout
    and pays no such copy.
    """
    R, D = dst.shape
    n = idx.shape[0]
    perm, sids, head = sorted_runs(idx)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[hbm, hbm],
        out_specs=hbm,
        scratch_shapes=[pltpu.VMEM((1, D), jnp.float32),     # run sum
                        pltpu.VMEM((1, D), jnp.float32),     # one row
                        pltpu.SemaphoreType.DMA((1,))],
    )
    out = pl.pallas_call(
        functools.partial(_scatter_add_kernel, n=n, R=R),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, 1, D), jnp.float32),
        input_output_aliases={3: 0},   # dst (first tensor operand) → out
        interpret=interpret,
        name="scatter_add_rows",
    )(sids, perm, head, lm.as_rows(dst.astype(jnp.float32)),
      lm.as_rows(rows.astype(jnp.float32)))
    return lm.from_rows(out).astype(dst.dtype)


# ---------------------------------------------------------------------------
# Fused backward megastep (recompute + cotangent math + scatter-add,
# one launch per reverse level)
# ---------------------------------------------------------------------------

def _bwd_megastep_kernel(cids_ref, eids_ref, off_ref, sid_ref, perm_ref,
                         head_ref, buf_ref, g_ref, ext_ref, nm_ref, *rest,
                         kind: str, A: int, bm: int, nb: int, n: int,
                         sentinel: int, nw: int, M: int, gated: bool):
    del g_ref  # read and written through the aliased output
    w_refs = rest[:nw]
    out_ref, stash_ref, chd, exv, gs, gch, acc, row, sem = rest[nw:]
    i = pl.program_id(0)
    S = gs.shape[-1]
    cotangent_step = i < nb
    if gated:
        # A block with no real vertex has no real edge: its stash rows
        # are never read, so it gathers, computes and writes nothing.
        cotangent_step = jnp.logical_and(
            cotangent_step, eids_ref[M + jnp.minimum(i, nb - 1)] != 0)

    # -- steps [0, nb): one block of bm slots → child cotangent tiles ---
    @pl.when(cotangent_step)
    def _cotangents():
        m0 = i * bm
        for a in range(A):
            lm.start_row_copies(buf_ref, chd.at[a], sem.at[0], bm,
                                lambda r, a=a: cids_ref[(m0 + r) * A + a])
        lm.start_row_copies(ext_ref, exv, sem.at[1], bm,
                            lambda r: eids_ref[m0 + r])
        # The level's own cotangent rows are never a destination at
        # this level (children live at levels < t): read them as-is.
        lm.copy_now(out_ref.at[pl.ds(off_ref[0] + m0, bm)], gs, sem.at[2])
        for a in range(A):
            lm.wait_row_copies(buf_ref, chd.at[a], sem.at[0], bm)
        lm.wait_row_copies(ext_ref, exv, sem.at[1], bm)
        children = [chd[a].reshape(bm, S).astype(jnp.float32)
                    for a in range(A)]
        g_state = (gs[...] * nm_ref[...]).reshape(bm, S).astype(jnp.float32)
        ext = exv[...].reshape(bm, exv.shape[-1]).astype(jnp.float32)
        weights = tuple(w[...] for w in w_refs)
        # Child masks are 1: absent children point at the zero sentinel,
        # their cotangent rows are never added (skipped below), and the
        # gathered zero rows already drop out of the cell math.
        g_child, _, _ = lm.level_bwd_tiles(kind, g_state, children, ext,
                                           [1.0] * A, weights)
        for a in range(A):
            gch[a] = g_child[a].reshape(bm, 1, S)
            lm.copy_now(gch.at[a], stash_ref.at[a, pl.ds(m0, bm)],
                        sem.at[2])

    # -- step nb: sorted-run scatter-add of the level's real edges -----
    # The sentinel is the largest id, so the sorted walk ends in its
    # run; with the flags it stops at the level's count of real edges.
    @pl.when(i == nb)
    def _scatter():
        _sorted_run_add(
            out_ref,
            lambda p: stash_ref.at[jax.lax.rem(p, A), jax.lax.div(p, A)],
            sid_ref, perm_ref, head_ref, n, lambda d: d != sentinel,
            acc, row, sem.at[3], count=eids_ref[M + nb] if gated else None)


def bwd_megastep(kind: str, g: jax.Array, buf: jax.Array,
                 child_ids: jax.Array, ext_ids: jax.Array,
                 node_mask: jax.Array, offset: jax.Array, ext: jax.Array,
                 weights: Tuple[jax.Array, ...], *,
                 sort_perm: jax.Array = None,
                 sorted_child_ids: jax.Array = None,
                 run_head: jax.Array = None,
                 interpret: bool = False) -> jax.Array:
    """One fused reverse batching task, in place.

    ``g``: ``[T*M+1, 1, S]`` gradient buffer in the row layout
    (aliased: the output IS this buffer with the child-row cotangents
    of level ``offset//M`` scatter-ADDED); ``buf``: the residual
    forward node buffer (gate recompute source, read-only, row
    layout); ``ext``: ``[E, 1, G]`` pulled rows (row layout);
    ``offset``: scalar ``t*M``.  Returns the updated gradient buffer;
    rows ``[offset, offset+M)``, the sentinel and every untouched row
    are preserved bit-exact.

    ``sort_perm`` / ``sorted_child_ids`` / ``run_head`` (each flat
    ``[M*A]``) are the level's precomputed sorted runs — ``pack_batch``
    computes them host-side with the rest of the schedule, so a training
    step pays ZERO on-device sorts.  When omitted (hand-built levels)
    they are derived here with one ``jnp.argsort``.

    ``ext_ids`` may be a level's row of ``level_megastep.live_ids``:
    blocks with no real vertex are then skipped, and the walk stops at
    the level's count of real edges.
    """
    M, A = child_ids.shape
    S = g.shape[-1]
    n = M * A
    sentinel = g.shape[0] - 1
    if sort_perm is None or sorted_child_ids is None or run_head is None:
        sort_perm, sorted_child_ids, run_head = sorted_runs(
            child_ids.reshape(-1))
    bm = lm.block_rows(M)
    nb, gated = lm.level_extent(M, ext_ids)
    if gated:
        # Dead blocks, and the walk's step, keep block 0's mask.
        def nm_block(i, c, e, *_):
            j = jnp.minimum(i, nb - 1)
            return jnp.where(e[M + j] != 0, j, 0), 0, 0
    else:
        def nm_block(i, *_):
            return jnp.minimum(i, nb - 1), 0, 0
    ws = lm.gate_weights(weights)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(nb + 1,),
        in_specs=[hbm, hbm, hbm,
                  pl.BlockSpec((bm, 1, 1), nm_block)]
        + [lm.resident(w) for w in ws],
        out_specs=[hbm, hbm],
        scratch_shapes=[pltpu.VMEM((A, bm, 1, S), buf.dtype),     # children
                        pltpu.VMEM((bm, 1, ext.shape[-1]), ext.dtype),
                        pltpu.VMEM((bm, 1, S), jnp.float32),      # g_state
                        pltpu.VMEM((A, bm, 1, S), jnp.float32),   # g_child
                        pltpu.VMEM((1, S), jnp.float32),          # run sum
                        pltpu.VMEM((1, S), jnp.float32),          # one row
                        pltpu.SemaphoreType.DMA((4,))],
    )
    out, _ = pl.pallas_call(
        functools.partial(_bwd_megastep_kernel, kind=kind, A=A, bm=bm,
                          nb=nb, n=n, sentinel=sentinel, nw=len(ws), M=M,
                          gated=gated),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(g.shape, g.dtype),
                   jax.ShapeDtypeStruct((A, M, 1, S), jnp.float32)),
        input_output_aliases={7: 0},   # g (second tensor operand) → out
        interpret=interpret,
        name="megastep_bwd",
    )(child_ids.reshape(-1).astype(jnp.int32), ext_ids.astype(jnp.int32),
      jnp.reshape(offset, (1,)).astype(jnp.int32),
      sorted_child_ids.astype(jnp.int32), sort_perm.astype(jnp.int32),
      run_head.astype(jnp.int32),
      buf, g, ext, (node_mask > 0).astype(g.dtype).reshape(M, 1, 1), *ws)
    return out
