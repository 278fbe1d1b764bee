"""Backend dispatch for the kernel layer.

Every op has three implementations:

  - ``pallas``   — the TPU kernel (``pl.pallas_call`` + BlockSpec);
                   interpret mode on non-TPU backends (exercised by the
                   test suite; too slow for CPU hot loops),
  - ``chunked``  — portable jnp with the *same blocking/memory profile*
                   as the kernel (what the CPU dry-run lowers),
  - ``ref``      — the naive oracle (``ref.py``).

``impl="auto"`` picks ``pallas`` on TPU and ``chunked`` (or ``ref`` for
ops whose oracle is already optimal under XLA, e.g. row gather) on CPU.
Set the env var ``REPRO_KERNEL_IMPL`` to pin a backend globally.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import cell_kernels, decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import gather_scatter as gsc
from repro.kernels import level_megastep as lm
from repro.kernels import mamba_ssd as ssd
from repro.kernels import ref
from repro.obs.registry import get_registry


def _default_impl() -> str:
    forced = os.environ.get("REPRO_KERNEL_IMPL")
    if forced:
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "chunked"


def _tick(op: str, impl: str) -> None:
    """Count one dispatch through this layer on the metrics registry
    (``kernel.dispatch{op=...,impl=...}``).  Dispatchers run at TRACE
    time inside jit, so this counts program builds per op/backend —
    which backend actually serves each op, and how often retracing
    happens — not per-step launches (``obs.profile`` censuses those)."""
    get_registry().inc("kernel.dispatch", op=op, impl=impl)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Fused cells
# ---------------------------------------------------------------------------

def lstm_gates(gates: jax.Array, c_prev: jax.Array,
               impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    impl = _default_impl() if impl == "auto" else impl
    _tick("lstm_gates", impl)
    if impl == "pallas":
        return cell_kernels.lstm_gates(gates, c_prev, interpret=_interpret())
    return ref.lstm_gates(gates, c_prev)


def lstm_level_fused(h_prev, c_prev, ext_proj, wh, b,
                     impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """One fused batching task: h_prev @ W_h + gates + state update
    (kernels/level_step.py — gates never round-trip HBM)."""
    impl = _default_impl() if impl == "auto" else impl
    _tick("lstm_level_fused", impl)
    if impl == "pallas":
        from repro.kernels import level_step
        return level_step.lstm_level_fused(h_prev, c_prev, ext_proj, wh, b,
                                           interpret=_interpret())
    return ref.lstm_level_fused(h_prev, c_prev, ext_proj, wh, b)


def treelstm_gates(i_pre, f_pre, o_pre, u_pre, c_k, child_mask,
                   impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    impl = _default_impl() if impl == "auto" else impl
    _tick("treelstm_gates", impl)
    if impl == "pallas":
        return cell_kernels.treelstm_gates(i_pre, f_pre, o_pre, u_pre, c_k,
                                           child_mask, interpret=_interpret())
    return ref.treelstm_gates(i_pre, f_pre, o_pre, u_pre, c_k, child_mask)


def level_megastep(kind: str, buf: jax.Array, child_ids: jax.Array,
                   child_mask: jax.Array, ext_ids: jax.Array,
                   node_mask: jax.Array, offset: jax.Array, ext: jax.Array,
                   weights: Tuple[jax.Array, ...],
                   impl: str = "auto") -> jax.Array:
    """One fused batching task: gather child rows out of ``buf``, run
    the declared gate math VMEM-resident, block-write rows
    ``[offset, offset+M)`` in place (kernels/level_megastep.py).

    ``buf`` (``[T*M+1, 1, S]``) and ``ext`` (``[E, 1, G]``) are in the
    row layout (``level_megastep.as_rows``); callers keep it across the
    level scan.  ``kind``/``weights`` come from the cell's
    ``GateSpec``.  The pallas backend is a single launch with the
    buffer aliased input→output; the fallback is the op-by-op oracle in
    ``ref.py`` (same math, same contiguous-block write, no fusion
    guarantee).
    """
    impl = _default_impl() if impl == "auto" else impl
    _tick("level_megastep", impl)
    if impl == "pallas":
        return lm.megastep(kind, buf, child_ids, ext_ids, node_mask, offset,
                           ext, weights, interpret=_interpret())
    return lm.as_rows(ref.level_megastep(
        kind, lm.from_rows(buf), child_ids, child_mask, ext_ids, node_mask,
        offset, lm.from_rows(ext), weights))


def frontier_megastep(kind: str, buf: jax.Array, child_ids: jax.Array,
                      child_mask: jax.Array, rows: jax.Array,
                      node_mask: jax.Array, out_ids: jax.Array,
                      weights: Tuple[jax.Array, ...],
                      impl: str = "auto") -> jax.Array:
    """One batching task over a mixed-depth UNION frontier (continuous
    serving): gather child rows from arbitrary arena rows of ``buf``
    (``[N, 1, S]``, row layout), run the declared gate math, scatter
    the masked states to the per-row destinations ``out_ids`` (unique;
    out-of-range = pad lane, dropped).  ``rows`` are the pre-gathered
    (eagerly projected) pulled rows ``[M, G]`` — per-request level
    offsets are resolved host-side by the engine, so the compiled
    program never changes.

    The pallas backend composes the two validated launches: the level
    megastep computes the frontier's states into a contiguous staging
    block appended past the buffer, then the scatter kernel routes them
    to their arena rows — two launches per tick (vs one for the
    depth-aligned path), the price of non-contiguous destinations.  The
    fallback is the jnp oracle (same row math as ``ref.level_megastep``
    — the bit-identity anchor for the continuous engine).
    """
    impl = _default_impl() if impl == "auto" else impl
    _tick("frontier_megastep", impl)
    if impl == "pallas":
        M = child_ids.shape[0]
        ncap = buf.shape[0]
        staged = jnp.concatenate(
            [buf, jnp.zeros((M,) + buf.shape[1:], buf.dtype)], axis=0)
        staged = lm.megastep(kind, staged, child_ids,
                             jnp.arange(M, dtype=jnp.int32), node_mask,
                             jnp.int32(ncap), lm.as_rows(rows), weights,
                             interpret=_interpret())
        return gsc.scatter_rows(buf, out_ids, staged[ncap:],
                                interpret=_interpret())
    return lm.as_rows(ref.frontier_megastep(
        kind, lm.from_rows(buf), child_ids, child_mask, rows, node_mask,
        out_ids, weights))


def bwd_megastep(kind: str, g: jax.Array, buf: jax.Array,
                 child_ids: jax.Array, child_mask: jax.Array,
                 ext_ids: jax.Array, node_mask: jax.Array,
                 offset: jax.Array, ext: jax.Array,
                 weights: Tuple[jax.Array, ...],
                 impl: str = "auto", *,
                 sort_perm: Optional[jax.Array] = None,
                 sorted_child_ids: Optional[jax.Array] = None,
                 run_head: Optional[jax.Array] = None) -> jax.Array:
    """One fused reverse batching task: recompute the level's gates from
    the residual node buffer ``buf``, run the cotangent math for the
    declared gate kind, and scatter-ADD the child-row cotangents into
    the gradient buffer ``g`` (∂gather = scatter-add, §3.4) — in ONE
    launch on the pallas backend (``kernels/level_megastep_bwd.py``,
    grad buffer aliased in place).  ``g``, ``buf`` and ``ext`` are in
    the row layout, as for :func:`level_megastep`.

    The ``chunked`` fallback is the pre-fusion oracle sweep: the
    analytic jnp ``level_megastep.level_bwd`` sandwiched between the
    gather and the XLA scatter-add (same math, same memory profile, no
    fusion guarantee); ``ref`` is plain autodiff of the naive cell
    forward (``ref.bwd_megastep``).

    ``sort_perm``/``sorted_child_ids``/``run_head``: the level's
    precomputed sorted runs (``pack_batch`` host-side output, carried in
    ``DeviceSchedule``) — when given, the pallas backend runs no device
    sort; the jnp fallbacks don't need them and ignore them.
    """
    impl = _default_impl() if impl == "auto" else impl
    _tick("bwd_megastep", impl)
    if impl == "pallas":
        from repro.kernels import level_megastep_bwd as lmb
        return lmb.bwd_megastep(kind, g, buf, child_ids, ext_ids, node_mask,
                                offset, ext, weights,
                                sort_perm=sort_perm,
                                sorted_child_ids=sorted_child_ids,
                                run_head=run_head, interpret=_interpret())
    g, buf, ext = lm.from_rows(g), lm.from_rows(buf), lm.from_rows(ext)
    if impl == "ref":
        return lm.as_rows(ref.bwd_megastep(kind, g, buf, child_ids,
                                           child_mask, ext_ids, node_mask,
                                           offset, ext, weights))
    M, A = child_ids.shape
    S = g.shape[1]
    g_state = jax.lax.dynamic_slice(g, (offset, 0), (M, S)) \
        * node_mask.astype(g.dtype)[:, None]
    child = jnp.take(buf, child_ids.reshape(-1), axis=0).reshape(M, A, S)
    rows = jnp.take(ext, ext_ids, axis=0)
    g_child, _, _ = lm.level_bwd(kind, g_state, child, rows, child_mask,
                                 weights)
    return lm.as_rows(ref.scatter_add_rows(
        g, child_ids.reshape(-1), g_child.reshape(M * A, S).astype(g.dtype)))


def scatter_add_rows(dst: jax.Array, idx: jax.Array, rows: jax.Array,
                     impl: str = "auto") -> jax.Array:
    """``dst[idx[i]] += rows[i]`` with repeats — ∂gather = scatter-add
    (§3.4), the megastep reverse sweep's memory op.  The pallas backend
    (kernels/level_megastep_bwd.py) walks the contributions in
    sorted-run order with the dst buffer aliased in place; the fallback
    is XLA's scatter-add.
    """
    impl = _default_impl() if impl == "auto" else impl
    _tick("scatter_add_rows", impl)
    if impl == "pallas":
        from repro.kernels import level_megastep_bwd as lmb
        return lmb.scatter_add_rows(dst, idx, rows, interpret=_interpret())
    return ref.scatter_add_rows(dst, idx, rows)


# ---------------------------------------------------------------------------
# Cavs primitives
# ---------------------------------------------------------------------------

def gather_rows(src: jax.Array, idx: jax.Array, impl: str = "auto") -> jax.Array:
    impl = _default_impl() if impl == "auto" else impl
    _tick("gather_rows", impl)
    if impl == "pallas":
        return lm.from_rows(gsc.gather_rows(lm.as_rows(src), idx,
                                            interpret=_interpret()))
    return ref.gather_rows(src, idx)


def scatter_rows(dst: jax.Array, idx: jax.Array, rows: jax.Array,
                 impl: str = "auto") -> jax.Array:
    impl = _default_impl() if impl == "auto" else impl
    _tick("scatter_rows", impl)
    if impl == "pallas":
        return lm.from_rows(gsc.scatter_rows(
            lm.as_rows(dst), idx, lm.as_rows(rows), interpret=_interpret()))
    return ref.scatter_rows(dst, idx, rows)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              scale: Optional[float] = None, impl: str = "auto",
              block_q: int = 512, block_k: int = 512) -> jax.Array:
    """``[B, Hq, Sq, D] × [B, Hkv, Sk, D]² → [B, Hq, Sq, D]``."""
    impl = _default_impl() if impl == "auto" else impl
    _tick("attention", impl)
    if impl == "pallas":
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  scale=scale, interpret=_interpret())
    if impl == "chunked":
        return fa.attention_chunked(q, k, v, causal=causal, window=window,
                                    scale=scale, block_q=block_q,
                                    block_k=block_k)
    return ref.mha(q, k, v, causal=causal, window=window, scale=scale)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     kv_len: Optional[jax.Array] = None,
                     window: Optional[int] = None,
                     scale: Optional[float] = None,
                     impl: str = "auto") -> jax.Array:
    """``[B, Hq, D] × [B, Hkv, S, D]² → [B, Hq, D]``."""
    impl = _default_impl() if impl == "auto" else impl
    _tick("decode_attention", impl)
    if impl == "pallas":
        return dec.decode_attention(q, k, v, kv_len=kv_len, window=window,
                                    scale=scale, interpret=_interpret())
    if impl == "chunked":
        return dec.decode_attention_chunked(q, k, v, kv_len=kv_len,
                                            window=window, scale=scale)
    return ref.decode_attention(q, k, v, kv_len=kv_len, window=window)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, D: Optional[jax.Array] = None, *,
        chunk: int = 128, initial_state: Optional[jax.Array] = None,
        impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """Chunked state-space-dual scan; returns ``(y, final_state)``."""
    impl = _default_impl() if impl == "auto" else impl
    _tick("ssd", impl)
    if impl == "ref":
        return ref.ssd_reference(x, dt, A, B, C, D,
                                 initial_state=initial_state)
    L = x.shape[1]
    c = min(chunk, L)
    # Pad the sequence to a chunk multiple.  Padding rows carry dt = 0:
    # decay = exp(0·A) = 1 and the input contribution dt·x⊗B = 0, so the
    # final state is exact; padded y rows are sliced off.
    Lp = (L + c - 1) // c * c
    if Lp != L:
        pad = ((0, 0), (0, Lp - L))
        x = jnp.pad(x, pad + ((0, 0), (0, 0)))
        dt = jnp.pad(dt, pad + ((0, 0),))
        B = jnp.pad(B, pad + ((0, 0),))
        C = jnp.pad(C, pad + ((0, 0),))
    if impl == "pallas":
        from repro.kernels import mamba_ssd
        y, s = mamba_ssd.ssd_chunk_scan(x, dt, A, B, C, D, chunk=c,
                                        initial_state=initial_state,
                                        interpret=_interpret())
    else:
        y, s = ref.ssd_chunked(x, dt, A, B, C, D, chunk=c,
                               initial_state=initial_state)
    return y[:, :L], s


def ssd_decode_step(x, dt, A, B, C, D, state):
    return ref.ssd_decode_step(x, dt, A, B, C, D, state)
