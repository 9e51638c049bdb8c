"""The plain reference's shared parts: the KV codec and the matmul precision.

Each configuration's block (`chipbench/blocks/<block>.py`) writes out its
published model in float32 jnp with no kernels, pages, cache or batching,
and reads its K/V through `kv_stored`: the form the serve path holds them
in.  With the codec (a cell's `serve.keep`), the K/V of every full 8-token
block is stored as its 8x8 tiles (8 positions x 8 channels) of orthonormal
DCT-II coefficients, the top-left keep x keep corner kept, quantised to
int8 with one scale per tile (max |coefficient| / 127), and read back
through the inverse DCT; without it (`serve.kv_compress` false) the raw K/V.

`matmul("control")` computes every product on float8_e4m3fn operands
(summed in float32): the step below the served bfloat16 that the
correctness check must tell apart from a sound run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 8


def dct_matrix() -> np.ndarray:
    """Orthonormal DCT-II: C[u, a] = s(u) cos(pi (2a + 1) u / 16)."""
    u = np.arange(BLOCK)[:, None]
    a = np.arange(BLOCK)[None, :]
    c = np.sqrt(2.0 / BLOCK) * np.cos(np.pi * (2 * a + 1) * u / (2 * BLOCK))
    c[0] /= np.sqrt(2.0)
    return c


def codec_roundtrip(x: jax.Array, keep: int) -> jax.Array:
    """x: (S, H, hd) with S % 8 == 0 -> what the store gives back."""
    s, h, hd = x.shape
    ck = jnp.asarray(dct_matrix()[:keep], jnp.float32)
    tiles = x.reshape(s // BLOCK, BLOCK, h, hd // BLOCK, BLOCK)
    z = jnp.einsum("ua,iahjb,vb->ihjuv", ck, tiles, ck)
    scale = jnp.maximum(jnp.max(jnp.abs(z), axis=(-2, -1), keepdims=True),
                        1e-8) / 127.0
    q = jnp.clip(jnp.round(z / scale), -127, 127)
    back = jnp.einsum("ua,ihjuv,vb->iahjb", ck, q * scale, ck)
    return back.reshape(s, h, hd)


def kv_stored(x: jax.Array, keep: int | None) -> jax.Array:
    """K or V (S, H, hd) as the serve path's store gives it back: through
    the codec at `keep`, or raw where the cell keeps raw K/V (None)."""
    return x if keep is None else codec_roundtrip(x, keep)


def matmul(precision: str):
    """einsum(spec, a, b) in the reference's precision, or the control's."""
    if precision == "control":
        f8 = jnp.float8_e4m3fn

        def mm(spec, a, b):
            a = a.astype(f8).astype(jnp.float32)
            b = b.astype(f8).astype(jnp.float32)
            return jnp.einsum(spec, a, b)
        return mm
    return lambda spec, a, b: jnp.einsum(spec, a, b)


def pad_len(n: int, quantum: int = 256) -> int:
    return -(-n // quantum) * quantum
