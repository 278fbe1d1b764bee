"""Pallas row gather/scatter — the Cavs primitives' TPU backend (§4).

Cavs implements ``gather``/``scatter``/``pull``/``push`` as one
customized ``memcpy`` kernel that moves many slices in a single launch.
The TPU rendering: scalar-prefetched indices address one DMA per row,
so whole rows move HBM→VMEM→HBM with zero gather arithmetic in the
vector units.  Arrays are in the row layout ``[R, 1, D]``
(``level_megastep.as_rows``), where any single row is a legal DMA.

``gather_rows``  : out[i] = src[idx[i]]
``scatter_rows`` : dst[idx[i]] = rows[i]   (unique indices; dst is
                   aliased in place, untouched rows preserved,
                   out-of-range indices dropped)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import level_megastep as lm


def _gather_kernel(idx_ref, src_ref, out_ref, rows, sem, *, bn: int):
    i0 = pl.program_id(0) * bn
    lm.start_row_copies(src_ref, rows, sem.at[0], bn,
                        lambda r: idx_ref[i0 + r])
    lm.wait_row_copies(src_ref, rows, sem.at[0], bn)
    lm.copy_now(rows, out_ref.at[pl.ds(i0, bn)], sem.at[1])


def gather_rows(src: jax.Array, idx: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """``src``: ``[R, 1, D]``; ``idx``: ``[n]`` int32 in ``[0, R)`` →
    ``[n, 1, D]``, ``block_rows(n)`` rows per grid step (one DMA per
    row into VMEM, one DMA writing the block)."""
    n = idx.shape[0]
    D = src.shape[-1]
    bn = lm.block_rows(n)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // bn,),
        in_specs=[hbm],
        out_specs=hbm,
        scratch_shapes=[pltpu.VMEM((bn, 1, D), src.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        functools.partial(_gather_kernel, bn=bn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, D), src.dtype),
        interpret=interpret,
        name="gather_rows",
    )(idx.astype(jnp.int32), src)


def _scatter_kernel(idx_ref, dst_ref, rows_ref, out_ref, rows, sem, *,
                    bn: int, R: int):
    del dst_ref  # rides along only for the alias
    i0 = pl.program_id(0) * bn
    lm.copy_now(rows_ref.at[pl.ds(i0, bn)], rows, sem.at[0])

    @pl.loop(0, bn)
    def _row(r):
        d = idx_ref[i0 + r]

        @pl.when(jnp.logical_and(d >= 0, d < R))
        def _write():
            lm.copy_now(rows.at[r], out_ref.at[d], sem.at[1])


def scatter_rows(dst: jax.Array, idx: jax.Array, rows: jax.Array, *,
                 interpret: bool = False,
                 name: str = "scatter_rows") -> jax.Array:
    """``dst``: ``[R, 1, D]``; ``idx``: ``[n]`` unique int32; ``rows``:
    ``[n, 1, D]`` → updated ``[R, 1, D]`` (functional; dst buffer
    aliased).  Indices outside ``[0, R)`` are dropped."""
    R, _, D = dst.shape
    n = idx.shape[0]
    bn = lm.block_rows(n)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // bn,),
        in_specs=[hbm, hbm],
        out_specs=hbm,
        scratch_shapes=[pltpu.VMEM((bn, 1, D), dst.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        functools.partial(_scatter_kernel, bn=bn, R=R),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst.shape, dst.dtype),
        input_output_aliases={1: 0},   # dst (first tensor operand) → out
        interpret=interpret,
        name=name,
    )(idx.astype(jnp.int32), dst, rows.astype(dst.dtype))
