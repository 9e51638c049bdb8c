"""Pallas TPU kernel: paper-exact two-step quantization + bitmap index.

Implements Eq. 7-8 (with the JPEG level shift, DESIGN.md §6) over a plane of
DCT coefficients: per grid tile, affine min-max quantization against the global
(fmin, fmax) range, Q-table division (the 8x8 table pre-tiled to the VMEM tile
shape so the divide is a plain elementwise op), zero detection for the 1-bit
index buffer, and a per-tile non-zero count for the compression-ratio
accounting — all in one VMEM pass, mirroring the paper's single computing
stream where quantization and encoding sit between the non-linear module and
the SRAM write port.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

BLOCK = 8


def _quant_pack_kernel(x_ref, rng_ref, qt_ref, q2_ref, idx_ref, nnz_ref, *, imax: int):
    x = x_ref[...].astype(jnp.float32)
    fmin = rng_ref[0, 0]
    fmax = rng_ref[0, 1]
    scale = imax / (fmax - fmin)
    q1 = jnp.clip(jnp.round((x - fmin) * scale), 0, imax)          # Eq. 7
    zp = jnp.round(jnp.clip(-fmin * scale, 0, imax))               # level shift
    q2 = jnp.round((q1 - zp) / qt_ref[...])                        # Eq. 8
    idx = (q2 != 0).astype(jnp.int8)
    q2_ref[...] = q2.astype(jnp.int32)
    idx_ref[...] = idx
    nnz_ref[0, 0] = jnp.sum(idx.astype(jnp.int32))


def quant_pack_plane_pallas(
    x: jax.Array,
    fmin,
    fmax,
    qt_plane: jax.Array,
    *,
    bits: int = 8,
    tile_r: int = 128,
    tile_c: int = 128,
    interpret: bool | None = None,
):
    """interpret=None: compiled on TPU, interpreted elsewhere."""
    interpret = resolve_interpret(interpret)
    r, c = x.shape
    assert r % BLOCK == 0 and c % BLOCK == 0
    tr = min(tile_r, r)
    tc = min(tile_c, c)
    pr = (-r) % tr
    pc = (-c) % tc
    xp = jnp.pad(x, ((0, pr), (0, pc))) if (pr or pc) else x
    qtp = (
        jnp.pad(qt_plane, ((0, pr), (0, pc)), constant_values=1.0)
        if (pr or pc)
        else qt_plane
    )
    rp, cp = xp.shape
    rng = jnp.array([[fmin, fmax]], jnp.float32)

    q2, idx, nnz = pl.pallas_call(
        functools.partial(_quant_pack_kernel, imax=(1 << bits) - 1),
        grid=(rp // tr, cp // tc),
        in_specs=[
            pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
            pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
            pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, cp), jnp.int32),
            jax.ShapeDtypeStruct((rp, cp), jnp.int8),
            jax.ShapeDtypeStruct((rp // tr, cp // tc), jnp.int32),
        ],
        interpret=interpret,
    )(xp, rng, qtp)
    # Padded blocks quantize the zero-pad: their q2 == round((zp-zp)/qt) == 0,
    # so they contribute nothing to nnz and slicing them off is exact.
    return q2[:r, :c], idx[:r, :c], jnp.sum(nnz)
