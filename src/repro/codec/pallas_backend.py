"""Fused Pallas `pallas` codec backend.

Wraps the raw kernels in `repro.kernels.*` with the policy decisions they
need: compile vs interpret, taken from `repro.codec.dispatch` instead of
being copy-pasted at every call site, and, under a mesh, a per-device
`shard_map` over the plane's block rows (`sharding.kernel_map`: GSPMD
cannot partition a Mosaic kernel).  The fused codec kernels take and
return the repo-canonical blocks layout ``(R/8, C/8, k, k)``, so consumers
only ever see one compressed representation regardless of backend.

This backend is the default on TPU (see dispatch.resolve_backend_name); on
CPU it runs the kernels in interpret mode, which the parity tests in
tests/test_codec_backends.py use to pin it against `reference`.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.codec import dispatch
from repro.core import quantize as quant_lib
from repro.kernels.dct8x8 import kernel as dct_kernel
from repro.kernels.fused_compress import kernel as fc_kernel
from repro.kernels.quant_pack import kernel as qp_kernel
from repro.parallel import sharding as sh

BLOCK = 8


class PallasBackend:
    name = "pallas"

    def __init__(self, interpret: bool | None = None):
        self._interpret = interpret  # None = auto (compiled on TPU only)

    @property
    def interpret(self) -> bool:
        return dispatch.resolve_interpret(self._interpret)

    def dct2_plane(self, x: jnp.ndarray, inverse: bool = False) -> jnp.ndarray:
        return dct_kernel.dct2_plane_pallas(
            x, inverse=inverse, interpret=self.interpret
        )

    def compress_plane(self, x: jnp.ndarray, keep: int):
        rows = sh.kernel_rows_axes(x.shape[0] // BLOCK)
        return sh.kernel_map(
            functools.partial(fc_kernel.compress_plane_pallas, keep=keep,
                              interpret=self.interpret),
            in_specs=P(rows, None),
            out_specs=(P(rows, None, None, None), P(rows, None)))(x)

    def decompress_plane(self, q: jnp.ndarray, scale: jnp.ndarray,
                         out_dtype=jnp.float32) -> jnp.ndarray:
        rows = sh.kernel_rows_axes(q.shape[0])
        return sh.kernel_map(
            functools.partial(fc_kernel.decompress_plane_pallas,
                              out_dtype=out_dtype, interpret=self.interpret),
            in_specs=(P(rows, None, None, None), P(rows, None)),
            out_specs=P(rows, None))(q, scale)

    def quant_pack_plane(self, x: jnp.ndarray, fmin, fmax, level: int,
                         bits: int = 8):
        qt_plane = quant_lib.qtable_plane(level, *x.shape)
        return qp_kernel.quant_pack_plane_pallas(
            x, fmin, fmax, qt_plane, bits=bits, interpret=self.interpret
        )
