"""Pallas TPU kernels: fused DCT + frequency-truncation + int8 quant codec.

This is the paper's "combine compression, decompression, and CNN acceleration
into one computing stream" adapted to TPU: activations make exactly ONE HBM
round-trip in compressed form; the transform+quant happens in VMEM at the
compute boundary.

Key identity: truncating Z = C X C^T to its kxk low-frequency corner equals

    packed = kron(I, C[:k,:]) @ X @ kron(I, C[:k,:])^T

i.e. fused DCT+truncation is two *skinny rectangular matmuls* with constant
operands — the compressed tile never exists in full 8x8 form.  Decompression
is the transpose pair.

The packed tile is block-major (row i*k+u, column j*k+v).  The per-block
amax needs the k x k corner of each block together, which on a TPU cannot
come from a reshape across the (sublane, lane) tiling.  So the kernel
de-interleaves with exact 0/1 selection matmuls instead: R_u = S_u @ packed
(rows u, u+k, ...) and Z_uv = R_u @ T_v (columns v, v+k, ...).  Each Z_uv
is the (TR/8, TC/8) plane of coefficient (u, v) of every block in the tile;
the amax is an elementwise max over the k^2 planes, and the tile is written
COEFFICIENT-MAJOR, (k, k, TR/8, TC/8).  Selection products add exact zeros,
so the codes are bit for bit those of the block-major formulation.

Grid: (R/TR, C/TC) with TR, TC = min(128, plane dim).  Each column tile
owns a leading index of the coefficient and scale arrays, so the tile's
block-column count is a full trailing dim and every block shape satisfies
the TPU (8, 128) rule at any plane width.  The wrappers take and return the
repo's blocks layout (R/8, C/8, k, k), transposing in XLA.
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
TILE = 128
_HI = jax.lax.Precision.HIGHEST


@functools.lru_cache(maxsize=None)
def block_diag_dct_rows_np(size: int, keep: int) -> np.ndarray:
    """kron(I_{size/8}, C8[:keep, :]) — fused DCT+truncate constant."""
    assert size % BLOCK == 0
    ck = _dct_matrix_np(BLOCK).astype(np.float32)[:keep, :]
    return np.kron(np.eye(size // BLOCK, dtype=np.float32), ck)


@functools.lru_cache(maxsize=None)
def select_rows_np(n: int, keep: int) -> np.ndarray:
    """(keep, n, n*keep) 0/1: slab u picks rows u, u+k, ... of a block-major
    (n*keep, .) packed tile; its transpose scatters them back."""
    sel = np.zeros((keep, n, n * keep), np.float32)
    for u in range(keep):
        sel[u, np.arange(n), np.arange(n) * keep + u] = 1.0
    return sel


def _dot(a, b):
    return jax.lax.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)


def _compress_kernel(x_ref, bdr_ref, bdct_ref, sr_ref, sct_ref,
                     q_ref, scale_ref, *, keep: int):
    x = x_ref[...].astype(jnp.float32)
    # fused DCT + corner extraction, block-major: (TR*k/8, TC*k/8)
    z = _dot(_dot(bdr_ref[...], x), bdct_ref[...])
    planes = []
    for u in range(keep):
        ru = _dot(sr_ref[u], z)                          # (TR/8, TC*k/8)
        planes += [_dot(ru, sct_ref[v]) for v in range(keep)]  # (TR/8, TC/8)
    amax = functools.reduce(jnp.maximum, [jnp.abs(p) for p in planes])
    scale = jnp.maximum(amax, 1e-8) / 127.0
    for i, p in enumerate(planes):
        q = jnp.clip(jnp.round(p / scale), -127, 127)
        q_ref[i // keep, i % keep] = q.astype(jnp.int8)
    scale_ref[...] = scale


def _decompress_kernel(q_ref, scale_ref, bdrt_ref, bdc_ref, srt_ref, sc_ref,
                       o_ref, *, keep: int):
    scale = scale_ref[...]                               # (TR/8, TC/8)
    z = None
    for u in range(keep):
        ru = None
        for v in range(keep):
            t = _dot(q_ref[u, v].astype(jnp.float32) * scale, sc_ref[v])
            ru = t if ru is None else ru + t             # (TR/8, TC*k/8)
        t = _dot(srt_ref[u], ru)                         # (TR*k/8, TC*k/8)
        z = t if z is None else z + t
    # X = bdr_k^T @ Z_packed @ bdc_k  (zero-pad corner + IDCT, fused)
    x = _dot(_dot(bdrt_ref[...], z), bdc_ref[...])
    o_ref[...] = x.astype(o_ref.dtype)


def _tiles(r: int, c: int) -> tuple[int, int, int, int]:
    tr, tc = min(TILE, r), min(TILE, c)
    return tr, tc, r + (-r) % tr, c + (-c) % tc


def _consts(tr: int, tc: int, keep: int):
    bdr = block_diag_dct_rows_np(tr, keep)
    bdc = block_diag_dct_rows_np(tc, keep)
    sr = select_rows_np(tr // BLOCK, keep)
    sc = select_rows_np(tc // BLOCK, keep)
    return bdr, bdc, sr, sc


def _const_spec(a: np.ndarray) -> pl.BlockSpec:
    return pl.BlockSpec(a.shape, lambda i, j: (0,) * a.ndim)


def compress_plane_pallas(
    x: jax.Array,
    keep: int,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(R, C) plane, R % 8 == C % 8 == 0 -> (coefs (R/8, C/8, k, k) int8,
    scale (R/8, C/8) f32).  interpret=None: compiled on TPU, interpreted
    elsewhere (`repro.kernels.resolve_interpret`)."""
    interpret = resolve_interpret(interpret)
    r, c = x.shape
    assert r % BLOCK == 0 and c % BLOCK == 0, (r, c)
    tr, tc, rp, cp = _tiles(r, c)
    xp = jnp.pad(x, ((0, rp - r), (0, cp - c))) if (rp, cp) != (r, c) else x
    nr, nc, nct = tr // BLOCK, tc // BLOCK, cp // tc
    bdr, bdc, sr, sc = _consts(tr, tc, keep)
    consts = (bdr, bdc.T, sr, np.swapaxes(sc, 1, 2))

    q, scale = pl.pallas_call(
        functools.partial(_compress_kernel, keep=keep),
        grid=(rp // tr, nct),
        in_specs=[pl.BlockSpec((tr, tc), lambda i, j: (i, j))]
        + [_const_spec(a) for a in consts],
        out_specs=[
            pl.BlockSpec((None, keep, keep, nr, nc),
                         lambda i, j: (j, 0, 0, i, 0)),
            pl.BlockSpec((None, nr, nc), lambda i, j: (j, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nct, keep, keep, rp // BLOCK, nc),
                                 jnp.int8),
            jax.ShapeDtypeStruct((nct, rp // BLOCK, nc), jnp.float32),
        ],
        interpret=interpret,
    )(xp, *(jnp.asarray(a) for a in consts))
    nh, nw = r // BLOCK, c // BLOCK
    # (nct, k, k, R/8, nc) -> (R/8, nct*nc, k, k) blocks layout
    coefs = jnp.transpose(q, (3, 0, 4, 1, 2)).reshape(rp // BLOCK, -1,
                                                       keep, keep)
    scale = jnp.transpose(scale, (1, 0, 2)).reshape(rp // BLOCK, -1)
    return coefs[:nh, :nw], scale[:nh, :nw]


def decompress_plane_pallas(
    coefs: jax.Array,
    scale: jax.Array,
    *,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
) -> jax.Array:
    """Inverse of `compress_plane_pallas`: (coefs (R/8, C/8, k, k), scale
    (R/8, C/8)) -> (R, C) plane in `out_dtype`."""
    interpret = resolve_interpret(interpret)
    nh, nw, keep, _ = coefs.shape
    r, c = nh * BLOCK, nw * BLOCK
    tr, tc, rp, cp = _tiles(r, c)
    if (rp, cp) != (r, c):
        pad = ((0, (rp - r) // BLOCK), (0, (cp - c) // BLOCK))
        coefs = jnp.pad(coefs, pad + ((0, 0), (0, 0)))
        scale = jnp.pad(scale, pad)
    nr, nc, nct = tr // BLOCK, tc // BLOCK, cp // tc
    # blocks layout -> (nct, k, k, R/8, nc) coefficient-major column tiles
    q = jnp.transpose(coefs.reshape(rp // BLOCK, nct, nc, keep, keep),
                      (1, 3, 4, 0, 2))
    scale = jnp.transpose(scale.reshape(rp // BLOCK, nct, nc), (1, 0, 2))
    bdr, bdc, sr, sc = _consts(tr, tc, keep)
    consts = (bdr.T, bdc, np.swapaxes(sr, 1, 2), sc)

    out = pl.pallas_call(
        functools.partial(_decompress_kernel, keep=keep),
        grid=(rp // tr, nct),
        in_specs=[
            pl.BlockSpec((None, keep, keep, nr, nc),
                         lambda i, j: (j, 0, 0, i, 0)),
            pl.BlockSpec((None, nr, nc), lambda i, j: (j, i, 0)),
        ] + [_const_spec(a) for a in consts],
        out_specs=pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, cp), out_dtype),
        interpret=interpret,
    )(q, scale, *(jnp.asarray(a) for a in consts))
    return out[:r, :c]
