"""Pallas TPU kernel: write flushed pages into one layer of a stacked row pool.

The paged pool stores every page plane as rows of 128 32-bit words
(`core.page_rows`), one array per row group, shape ``(L, rows, 1, 128)``.
A decode step flushes at most one page per batch row and layer; this kernel
writes those pages IN PLACE: the pool is aliased to the output and pinned
to HBM, and each flushing row's rows go out in one DMA at
``[layer, row]``.  Non-flushing rows carry row -1 and move nothing.

A row group whose pages share a row (`RowGroup.pages_per_row` > 1) is a
read-modify-write: every touched row is read into VMEM first, the masked
lanes are replaced, and the rows are written back.  The caller merges the
updates of pages that share a row, so two DMAs that hit one row carry the
same bytes.

Why a kernel and not an XLA scatter: XLA lowers a scatter into a stacked
pool to a loop over the updates, and the TPU compiler may then move the
whole array into fast memory and back around that loop, once per layer.
A custom call keeps the pool where it is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _write_kernel(row_ref, layer_ref, upd_ref, *refs, n: int, merge: bool):
    if merge:
        mask_ref, _, out_ref, buf_ref, sems = refs
    else:
        _, out_ref, sems = refs
    layer = layer_ref[0]
    r = upd_ref.shape[1]

    def each(fn):       # fn(i) for every row that writes, in one loop
        def body(i, carry):
            pl.when(row_ref[i] >= 0)(functools.partial(fn, i))
            return carry
        jax.lax.fori_loop(0, n, body, 0)

    def dma(i, src, dst):
        return pltpu.make_async_copy(src, dst, sems.at[i])

    src = upd_ref
    if merge:
        read = lambda i: dma(i, out_ref.at[layer, pl.ds(row_ref[i], 1)],
                             buf_ref.at[i])
        each(lambda i: read(i).start())
        each(lambda i: read(i).wait())
        buf_ref[...] = jnp.where(mask_ref[...] != 0, upd_ref[...],
                                 buf_ref[...])
        src = buf_ref
    write = lambda i: dma(i, src.at[i], out_ref.at[layer, pl.ds(row_ref[i], r)])
    each(lambda i: write(i).start())
    each(lambda i: write(i).wait())


def write_rows(
    pool: jax.Array,        # (L, rows, 1, 128) 32-bit row array
    layer: jax.Array,       # () int32
    rows: jax.Array,        # (N,) int32 first row per update; -1 = none
    upd: jax.Array,         # (N, R, 1, 128) pool dtype: the rows to write
    mask: jax.Array | None = None,  # (N, 1, 1, 128) int32: lanes to replace
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Write ``upd[i]`` at ``pool[layer, rows[i] : rows[i] + R]`` for every
    ``rows[i] >= 0``; with `mask` (R = 1) replace only the lanes where it is
    non-zero.  The pool is donated to the result (updated in place)."""
    n = upd.shape[0]
    merge = mask is not None
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    ins = [vmem] + ([vmem] if merge else []) + [hbm]
    scratch = ([pltpu.VMEM((n, 1, 1, 128), pool.dtype)] if merge else []) \
        + [pltpu.SemaphoreType.DMA((n,))]
    args = [upd.astype(pool.dtype)] + ([mask] if merge else []) + [pool]
    return pl.pallas_call(
        functools.partial(_write_kernel, n=n, merge=merge),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(), in_specs=ins,
            out_specs=hbm, scratch_shapes=scratch),
        out_shape=pltpu.HBM(pool.shape, pool.dtype),
        input_output_aliases={1 + len(args): 0},   # the pool (after 2 scalars)
        interpret=resolve_interpret(interpret),
        name="page_write",
    )(rows.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1), *args)
