"""Pallas TPU kernel: blocked 8x8 2-D DCT/IDCT over a plane.

TPU mapping (DESIGN.md §2): transforming every aligned 8x8 block of a (R, C)
plane is expressed as two *dense* matmuls with block-diagonal constants,

    Z = kron(I_{TR/8}, C) @ X @ kron(I_{TC/8}, C)^T

so the kernel is two MXU matmuls per tile — no transposes, no gathers, the
constant operand stays resident in VMEM across the whole grid.  The MXU is a
fixed-function 128x128 systolic array: a block-diagonal 128x128 operand runs at
the same rate as a dense one, so this formulation is time-optimal on TPU even
though 7/8 of the multiplier lanes carry zeros (the paper's CCM array makes the
same trade the other way: constant-coefficient multipliers with zero-gating).

Grid: (R/TR, C/TC).  VMEM per step: TR*TC*(2 tiles) + TR^2 + TC^2 floats —
TR=TC=128 => ~200 KB of f32, comfortably inside the ~16 MB VMEM budget, with
room for double-buffered pipelining by the Pallas runtime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.dct import _dct_matrix_np
from repro.kernels import resolve_interpret

BLOCK = 8


@functools.lru_cache(maxsize=None)
def block_diag_dct_np(size: int) -> np.ndarray:
    """kron(I_{size/8}, C8) as float32 — the per-tile constant operand."""
    assert size % BLOCK == 0
    c = _dct_matrix_np(BLOCK).astype(np.float32)
    return np.kron(np.eye(size // BLOCK, dtype=np.float32), c)


def _dct_tile_kernel(x_ref, bdr_ref, bdc_ref, o_ref, *, inverse: bool):
    x = x_ref[...].astype(jnp.float32)
    bdr = bdr_ref[...]
    bdc = bdc_ref[...]
    if inverse:
        # X = BDr^T Z BDc  (Eq. 6 lifted to the block-diagonal form)
        y = jax.lax.dot(bdr.T, x, preferred_element_type=jnp.float32)
        y = jax.lax.dot(y, bdc, preferred_element_type=jnp.float32)
    else:
        # Z = BDr X BDc^T  (Eq. 5)
        y = jax.lax.dot(bdr, x, preferred_element_type=jnp.float32)
        y = jax.lax.dot(y, bdc.T, preferred_element_type=jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def dct2_plane_pallas(
    x: jax.Array,
    *,
    inverse: bool = False,
    tile_r: int = 128,
    tile_c: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Blocked 2-D DCT of a (R, C) plane; R, C multiples of 8.

    Pads to tile multiples (zero padding only ever adds whole 8x8 blocks whose
    coefficients are sliced off again), runs the tiled Pallas kernel.
    interpret=None: compiled on TPU, interpreted elsewhere.
    """
    interpret = resolve_interpret(interpret)
    r, c = x.shape
    assert r % BLOCK == 0 and c % BLOCK == 0, (r, c)
    tr = min(tile_r, r)
    tc = min(tile_c, c)
    pr = (-r) % tr
    pc = (-c) % tc
    xp = jnp.pad(x, ((0, pr), (0, pc))) if (pr or pc) else x
    rp, cp = xp.shape

    bdr = jnp.asarray(block_diag_dct_np(tr))
    bdc = jnp.asarray(block_diag_dct_np(tc))

    out = pl.pallas_call(
        functools.partial(_dct_tile_kernel, inverse=inverse),
        grid=(rp // tr, cp // tc),
        in_specs=[
            pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
            pl.BlockSpec((tr, tr), lambda i, j: (0, 0)),
            pl.BlockSpec((tc, tc), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, cp), x.dtype),
        interpret=interpret,
    )(xp, bdr, bdc)
    return out[:r, :c]
