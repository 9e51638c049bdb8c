"""jit-able wrapper: fused kernel over all (batch, kv-head) planes — the
drop-in decode attention for the compressed KV cache.  The dense-plane path
merges the raw tail here in XLA; the paged kernel fuses the tail merge into
its finalize step and returns the normalized output directly."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.codec import dispatch as codec_dispatch
from repro.core import page_rows
from repro.core.kv_cache import as_pos_vec
from repro.kernels.fused_attend.kernel import (
    attend_compressed_plane,
    attend_paged,
)
from repro.parallel import sharding as sh
from repro.parallel.sharding import attn_hint

BLOCK = 8


def attend_with_tail(
    q: jax.Array,                 # (B, 1, H, hd)
    layer_cache: dict,            # per-layer compressed cache slices
    pos: jax.Array,               # (B,) per-slot positions (scalar broadcasts)
    *,
    tile_s: int = 512,
    interpret: bool | None = None,
    block_table: jax.Array | None = None,  # (B, nblocks) page ids (paged)
    pages_per_tile: int = 8,
    keep: int | None = None,               # paged: the segment's keep
    layer: jax.Array | int = 0,            # paged: layer of the stacked rows
    n_pages: int | None = None,            # paged: pages in the pool
) -> jax.Array:
    """Kernel-backed equivalent of core.kv_cache.attend_compressed.

    `pos` is a per-slot vector: the batch vmap maps it alongside the cache
    planes, so every row's kernel invocation masks against that row's own
    flushed watermark. interpret=None auto-selects via the codec dispatch
    rules: compiled on TPU, interpret elsewhere (CPU CI).

    With `block_table` the cache holds the shared page pool — the
    segment's stacked row arrays (`core.page_rows`) beside this layer's
    tails — and the fused paged kernel fetches G pages per grid step by
    (`layer`, page id), ids and layer on the scalar-prefetch path, and
    merges the raw tail in its finalize step — one pallas_call emits the
    normalized output.  The table may be a decode-ladder bucket slice of
    the full table (see core.kv_cache.table_view): the kernel grid covers
    only the slice.
    """
    interpret = codec_dispatch.resolve_interpret(interpret)
    b, _, h, hd = q.shape
    hkv = layer_cache["tail_k"].shape[-2]
    n_rep = h // hkv
    pos = as_pos_vec(pos, b)
    qg = q[:, 0].reshape(b, hkv, n_rep, hd)

    if block_table is not None:
        layout = page_rows.row_layout("dct", keep, hkv, hd)
        codes = layer_cache[page_rows.CODES]
        scales = layer_cache[page_rows.ROW_PREFIX + "scale"]
        layer = jnp.asarray(layer, jnp.int32)
        if sh.ambient_mesh() is not None:
            # under a mesh each device attends its own slots against the
            # whole pool: a slot's pages can sit on any data shard (the
            # allocator is device-agnostic), so this layer's rows enter the
            # kernel replicated
            codes = jax.lax.dynamic_index_in_dim(codes, layer, 0)
            scales = jax.lax.dynamic_index_in_dim(scales, layer, 0)
            layer = jnp.int32(0)
        rows = sh.kernel_rows_axes(b)
        out = sh.kernel_map(
            functools.partial(attend_paged, layout=layout, n_pages=n_pages,
                              pages_per_tile=pages_per_tile,
                              interpret=interpret),
            in_specs=(P(rows), P(rows), P(), P(), P(), P(rows), P(rows),
                      P(rows)),
            out_specs=P(rows),
        )(pos, block_table, layer, codes, scales, qg,
          layer_cache["tail_k"], layer_cache["tail_v"],
          )  # (B, Hkv, n_rep, hd) normalized — tail merged in-kernel
        return attn_hint(out.reshape(b, 1, h, hd).astype(q.dtype))

    # (B, S/8, Hkv, hd/8, k, k) -> planes (B, Hkv, S/8, hd/8, k, k)
    def plane_axes(x):
        return jnp.swapaxes(x, 1, 2)

    kern = functools.partial(attend_compressed_plane, tile_s=tile_s,
                             interpret=interpret)
    # vmap over batch (pos mapped: per-slot horizon) then kv-head
    # (shared pos)
    acc, m, l = jax.vmap(jax.vmap(kern, in_axes=(0, 0, 0, 0, 0, None)),
                         in_axes=(0, 0, 0, 0, 0, 0))(
        plane_axes(layer_cache["packed_k"]), plane_axes(layer_cache["scale_k"]),
        plane_axes(layer_cache["packed_v"]), plane_axes(layer_cache["scale_v"]),
        qg, pos,
    )  # acc (B, Hkv, n_rep, hd), m/l (B, Hkv, n_rep, 1)

    # ---- merge the raw tail (positions pos//8*8 .. pos, per row) ----------
    tk = jnp.swapaxes(layer_cache["tail_k"], 1, 2).astype(jnp.float32)  # (B,Hkv,8,hd)
    tv = jnp.swapaxes(layer_cache["tail_v"], 1, 2).astype(jnp.float32)
    qf = qg.astype(jnp.float32) / np.sqrt(hd)
    st = jnp.einsum("bgrd,bgtd->bgrt", qf, tk)          # (B, Hkv, rep, 8)
    flushed = (pos // BLOCK) * BLOCK
    tail_pos = flushed[:, None] + jnp.arange(BLOCK)     # (B, 8)
    tvalid = (tail_pos <= pos[:, None])[:, None, None]  # (B, 1, 1, 8)
    st = jnp.where(tvalid, st, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(st, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    pt = jnp.where(tvalid, jnp.exp(st - m_safe), 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l2 = l * alpha + jnp.sum(pt, axis=-1, keepdims=True)
    acc2 = acc * alpha + jnp.einsum("bgrt,bgtd->bgrd", pt, tv)
    out = acc2 / jnp.maximum(l2, 1e-30)
    # under a serve mesh keep the merged output head-sharded like the packed
    # planes it came from (slots on data, heads on model when divisible)
    return attn_hint(out.reshape(b, 1, h, hd).astype(q.dtype))
