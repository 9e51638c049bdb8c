"""KVCompress: DCT-truncated int8 KV cache (DESIGN.md §3.2).

The paper stores interlayer feature maps compressed so the expensive memory
level never holds raw data. In serving, the analogous storage is the KV
cache: at 32k-512k contexts it dominates HBM capacity AND decode-step HBM
bandwidth (every step re-reads the whole cache).

Layout: per (layer, batch, kv-head) the (S, hd) plane is tiled into 8x8
(seq-block x feature-block) tiles; each tile keeps only its top-left k x k
low-frequency DCT corner as int8 with a per-tile f32 scale:

  packed : (L, B, S/8, hd/8, k, k) int8
  scale  : (L, B, S/8, hd/8)       f32

Compressed bytes/elem = (k*k + 4) / 64 vs 2 (bf16): k=4 -> 0.31 B (6.4x),
k=6 -> 0.63 B (3.2x).  Because decode is memory-bound, the bandwidth saving
is the same factor — that is the paper's DMA-bandwidth argument verbatim.

The kept corner size k is PER LAYER: a `repro.codec.plan.CompressionPlan`
resolves a `LayerPolicy` per layer index (the paper's per-layer 2-bit
compression-level register), and the cache materializes it as a tuple of
`KVSegment`s — one stacked store per contiguous run of layers with equal
policy, each with its own (k, k) block geometry.  Uniform plans collapse to
a single segment, and the legacy `keep=` scalar is a one-line shim for
`CompressionPlan.uniform(keep)`.

Decode appends single tokens, which don't fill an 8-token seq block, so the
cache keeps a RAW TAIL of up to 8 tokens; when the tail fills, the whole
block is DCT-compressed into the packed store.  Positions are PER SLOT:
`pos` is a (B,) vector (scalars broadcast), so each batch row has its own
tail slot, its own flush decision (scatter writes with masked row indices;
one global cond only skips the codec when no row flushes), and its own
causal validity mask.  This is what lets the serve
engine retire and re-admit requests slot-by-slot (continuous batching) over
one shared compressed pool — the serving analogue of the paper's dynamic
feature-map buffer allocation.
Attention consumes the packed store via `attend_compressed`, which
decompresses per KV chunk INSIDE the flash-attention scan — the HBM traffic
for history is int8 packed + scales only, mirroring the paper's "IDCT fused
into the PE stream".

PAGED POOL (the paper's dynamic feature-map buffer allocation, literally):
instead of a dense per-slot `(B, S/8, ...)` store provisioned for max_seq,
`PagedKVCache` keeps a shared page pool whose page unit is ONE 8-token DCT
block group across all layers — per segment the family's planes stored as
rows of 128 32-bit words (`core.page_rows`: `rows_codes (Lseg, rows, 1,
128)` int32 and `rows_scale` f32 for dct) — addressed through a per-slot
block table `(B, S/8) -> page id`.
Because every layer of a slot flushes the same block index at the same step
(one position vector drives them all), a single block-table entry covers
all layers.  Pages are assigned by the HOST (the serve engine owns the free
list — allocation policy never enters the jit); the device only scatters
through the page index it is handed (`flush_page`) and gathers history
through the block table.  Unmapped table entries stay 0 — a valid page —
and are never read because attention masks `kv_pos < flushed` first.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import codec as codec_lib
from repro.codec import families as families_lib
from repro.codec import plan as plan_lib
from repro.core import page_rows
from repro.parallel.sharding import (ambient_mesh, attn_hint,
                                     logical as shard_hint, table_slice_hint)

BLOCK = 8

# raw per-slot tail ring planes — outside every codec family's plane tree
TAIL_NAMES = families_lib.TAIL_NAMES


def block_group_bytes(keep: int, n_kv_heads: int, head_dim: int,
                      codec: str = "dct") -> int:
    """Analytic bytes of one flushed 8-token block group for ONE layer, K
    and V — the codec family's `analytic_tile_bytes` applied to the cache
    geometry (for dct exactly `codec.api.tile_bytes`).  This is the
    page-size unit of the paged pool and the per-block term of every
    analytic pool report."""
    assert head_dim % BLOCK == 0, head_dim
    fam = families_lib.get_family(codec)
    return 2 * n_kv_heads * (head_dim // BLOCK) * fam.analytic_tile_bytes(keep)


def as_pos_vec(pos: jax.Array | int, batch: int) -> jax.Array:
    """Normalize a position argument to a per-slot (B,) int32 vector.

    Scalars (the legacy lock-step API) broadcast to every row; (B,) vectors
    pass through, giving each batch slot its own absolute position.
    """
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        return jnp.broadcast_to(pos, (batch,))
    assert pos.shape == (batch,), (pos.shape, batch)
    return pos


# ---------------------------------------------------------------------------
# Tile codec on (S, hd) planes with arbitrary leading dims — thin wrappers
# over the unified codec dispatch (reference einsum on CPU, fused Pallas on
# TPU; override via backend=/REPRO_CODEC_BACKEND).
# ---------------------------------------------------------------------------

def compress_kv_blocks(x: jax.Array, keep: int,
                       backend: str | None = None) -> tuple[jax.Array, jax.Array]:
    """x: (..., S, hd) with S % 8 == 0, hd % 8 == 0.

    Returns (packed (..., S/8, hd/8, k, k) int8, scale (..., S/8, hd/8) f32).
    """
    return codec_lib.compress_blocks(x, keep, backend=backend)


def decompress_kv_blocks(packed: jax.Array, scale: jax.Array, dtype=jnp.bfloat16,
                         backend: str | None = None) -> jax.Array:
    """Inverse of compress_kv_blocks -> (..., S, hd)."""
    return codec_lib.decompress_blocks(packed, scale, out_dtype=dtype,
                                       backend=backend)


# ---------------------------------------------------------------------------
# Cache container
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_with_keys_class
@dataclass
class KVSegment:
    """Compressed store for one contiguous run of layers sharing a policy.

    `planes` holds every storage array this segment's codec FAMILY declares,
    materialized once for K and once for V as ``{name}_k`` / ``{name}_v``
    (plus the family-independent raw tail ring ``tail_k`` / ``tail_v``).
    Shapes (GQA; Lseg = stop - start layers; block_shape per
    `families.PlaneSpec`):

      {name}_k/v : (Lseg, B, S/8, Hkv) + block_shape   e.g. dct packed ->
                   (Lseg, B, S/8, Hkv, hd/8, k, k) int8, scale ->
                   (Lseg, B, S/8, Hkv, hd/8) f32
      tail_k/v   : (Lseg, B, 8, Hkv, hd) raw dtype

    Registered WITH key paths so `parallel.sharding.cache_specs` can
    dispatch on each plane's name straight off the cache pytree — one spec
    rule set covers the dict form (dry-run) and the segment form (serve
    engine).  Flatten order is sorted-by-name so segments of equal plan are
    structurally equal pytrees.
    """

    planes: dict[str, jax.Array]
    keep: int                  # static: this segment's kept corner size
    start: int                 # static: absolute first layer
    stop: int                  # static: absolute one-past-last layer
    backend: str | None = None  # static: codec backend (None = auto)
    codec: str = "dct"          # static: codec family (plane tree owner)

    def __post_init__(self):
        # legacy positional-array construction died with _SEGMENT_FIELDS
        assert isinstance(self.planes, dict), type(self.planes)

    def _names(self) -> tuple[str, ...]:
        return tuple(sorted(self.planes))

    def tree_flatten(self):
        names = self._names()
        return tuple(self.planes[n] for n in names), \
            (names, self.keep, self.start, self.stop, self.backend,
             self.codec)

    def tree_flatten_with_keys(self):
        ga = jax.tree_util.GetAttrKey
        names = self._names()
        return tuple((ga(n), self.planes[n]) for n in names), \
            (names, self.keep, self.start, self.stop, self.backend,
             self.codec)

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, *rest = aux
        return cls(dict(zip(names, children)), *rest)

    # legacy single-plane views (the pre-family 4+2 field names)
    packed_k = property(lambda self: self.planes["packed_k"])
    scale_k = property(lambda self: self.planes["scale_k"])
    packed_v = property(lambda self: self.planes["packed_v"])
    scale_v = property(lambda self: self.planes["scale_v"])
    tail_k = property(lambda self: self.planes["tail_k"])
    tail_v = property(lambda self: self.planes["tail_v"])

    @property
    def family(self) -> families_lib.CodecFamily:
        return families_lib.get_family(self.codec)

    @property
    def paged(self) -> bool:
        """Whether the block planes are page-pool rows (`core.page_rows`)."""
        return any(page_rows.is_row_key(n) for n in self.planes)

    @property
    def page_keys(self) -> tuple[str, ...]:
        """Names of the block planes a page carries (every plane the family
        declares, ``{name}_k`` / ``{name}_v``; tails stay per slot) — the
        keys of the page update trees, whatever the storage."""
        if self.paged:
            return page_rows.segment_layout(self).plane_names
        return tuple(n for n in self._names() if n not in TAIL_NAMES)

    @property
    def row_keys(self) -> tuple[str, ...]:
        """Names of a paged segment's stored row arrays."""
        return tuple(n for n in self._names() if page_rows.is_row_key(n))

    def as_tree(self) -> dict[str, jax.Array]:
        """The {packed_k, ..., tail_v} dict layer-sliceable consumers scan."""
        return dict(self.planes)

    def replace_arrays(self, tree: dict[str, jax.Array]) -> "KVSegment":
        assert sorted(tree) == list(self._names()), (sorted(tree), self._names())
        return KVSegment(dict(tree), self.keep, self.start, self.stop,
                         self.backend, self.codec)

    def nbytes(self) -> float:
        """Device bytes held by this segment's planes — the literal sum of
        the array buffers.  For a dense dct segment this equals the
        analytic `codec.api.tile_bytes` charge exactly (int8 corner + 4-byte
        f32 scale header, nothing else), so the pool report cannot drift
        from the codec accounting; tests/test_plan.py pins that identity.
        A paged segment's rows add their padding, if any (`page_rows`).
        """
        return float(sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                         for a in self.planes.values()))


@jax.tree_util.register_pytree_with_keys_class
@dataclass
class CompressedKVCache:
    """Per-model compressed KV store: a tuple of per-policy `KVSegment`s.

    A uniform plan yields exactly one segment; the `packed_k`/.../`keep`
    properties then expose its planes directly (the legacy single-store
    view most tests and single-layer consumers use).  Non-uniform plans
    have per-segment block geometry — iterate `segments`.
    """

    segments: tuple[KVSegment, ...]

    def tree_flatten(self):
        return (self.segments,), ()

    def tree_flatten_with_keys(self):
        return ((jax.tree_util.GetAttrKey("segments"), self.segments),), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children[0]))

    @classmethod
    def from_arrays(cls, packed_k, scale_k, packed_v, scale_v, tail_k, tail_v,
                    keep: int, backend: str | None = None) -> "CompressedKVCache":
        """Single-segment (uniform-plan, dct) cache from bare (L, B, ...)
        planes — the legacy constructor shape, for consumers that flatten
        the cache into its planes and rebuild it (e.g. the dry-run sharding
        driver)."""
        planes = dict(packed_k=packed_k, scale_k=scale_k, packed_v=packed_v,
                      scale_v=scale_v, tail_k=tail_k, tail_v=tail_v)
        return cls((KVSegment(planes, keep=keep, start=0,
                              stop=packed_k.shape[0], backend=backend),))

    def _single(self) -> KVSegment:
        if len(self.segments) != 1:
            raise ValueError(
                "cache has per-layer block geometry; iterate cache.segments")
        return self.segments[0]

    packed_k = property(lambda self: self._single().packed_k)
    scale_k = property(lambda self: self._single().scale_k)
    packed_v = property(lambda self: self._single().packed_v)
    scale_v = property(lambda self: self._single().scale_v)
    tail_k = property(lambda self: self._single().tail_k)
    tail_v = property(lambda self: self._single().tail_v)
    keep = property(lambda self: self._single().keep)

    @property
    def n_layers(self) -> int:
        return self.segments[-1].stop

    @property
    def keeps(self) -> tuple[int, ...]:
        """Per-layer kept corner sizes (the materialized plan)."""
        return tuple(s.keep for s in self.segments
                     for _ in range(s.stop - s.start))

    @property
    def codecs(self) -> tuple[str, ...]:
        """Per-layer codec family names (the materialized plan)."""
        return tuple(s.codec for s in self.segments
                     for _ in range(s.stop - s.start))

    @property
    def max_seq(self) -> int:
        return self.segments[0].packed_k.shape[2] * BLOCK

    def nbytes_per_token_per_layer(self) -> float:
        """Mean analytic compressed bytes per token per layer (K and V)."""
        total = 0.0
        for s in self.segments:
            _, _, _, hkv, nhd, k, _ = s.packed_k.shape
            total += (s.stop - s.start) * \
                block_group_bytes(k, hkv, nhd * BLOCK, codec=s.codec) / BLOCK
        return total / self.n_layers

    def storage_stats(self, raw_dtype_bytes: int = 2) -> dict:
        """Honest footprint of the pool vs a raw bf16 cache of equal shape."""
        seg = self.segments[0]
        _, b, ns, hkv, nhd, _, _ = seg.packed_k.shape
        hd = nhd * BLOCK
        kv_bytes = sum(s.nbytes() for s in self.segments)
        raw = self.n_layers * b * (ns * BLOCK) * hkv * hd * raw_dtype_bytes * 2
        return {
            "kv_bytes": kv_bytes,
            "raw_bytes": float(raw),
            "ratio": kv_bytes / raw,
            "keeps": self.keeps,
        }


def _segment_planes(pol, n_layers: int, prefix: tuple[int, ...], batch: int,
                    hkv: int, hd: int, dtype) -> dict[str, jax.Array]:
    """Zero planes for one segment from its family's declared plane tree.

    `prefix` is the cache layout's per-plane leading shape AFTER the layer
    axis and BEFORE the Hkv axis: (batch, S/8) dense, (n_pages,) paged.
    """
    fam = families_lib.get_family(pol.codec)
    planes: dict[str, jax.Array] = {}
    for spec in fam.plane_specs(pol.kv_keep, hd):
        shape = (n_layers,) + prefix + (hkv,) + spec.block_shape
        for sfx in ("_k", "_v"):
            planes[spec.name + sfx] = jnp.zeros(shape, spec.dtype)
    for name in TAIL_NAMES:
        planes[name] = jnp.zeros((n_layers, batch, BLOCK, hkv, hd), dtype)
    return planes


def init_compressed_cache(cfg, batch: int, max_seq: int, keep: int = 4,
                          dtype=jnp.bfloat16,
                          plan=None) -> CompressedKVCache:
    """Allocate the pool per `plan` (legacy scalar `keep` => uniform plan)."""
    assert max_seq % BLOCK == 0
    hd = cfg.resolved_head_dim
    assert hd % BLOCK == 0, f"head_dim {hd} not 8-tileable"
    plan = plan_lib.as_plan(plan, keep=keep)
    hkv = cfg.n_kv_heads
    ns = max_seq // BLOCK
    segments = []
    for start, stop, pol in plan.segments(cfg.n_layers):
        planes = _segment_planes(pol, stop - start, (batch, ns), batch,
                                 hkv, hd, dtype)
        segments.append(KVSegment(planes, keep=pol.kv_keep, start=start,
                                  stop=stop, backend=pol.backend,
                                  codec=pol.codec))
    return CompressedKVCache(tuple(segments))


# ---------------------------------------------------------------------------
# Paged pool container (dynamic block-granular allocation)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_with_keys_class
@dataclass
class PagedKVCache:
    """Shared page pool + per-slot block tables.

    `segments` are ordinary `KVSegment`s whose storage planes are PAGE
    pools instead of per-slot stores (tails stay per slot — an 8-token raw
    ring is not worth paging).  A page's planes are stored as rows of 128
    32-bit words (`core.page_rows`), for dct:

      rows_codes : (Lseg, P * R, 1, 128) int32  a page's int8 corners, K
                   then V, all KV heads, 4 to a word: R = ceil(2 * Hkv *
                   hd/8 * k * ceil(k/4) / 128) rows (1 for qwen2-0.5B's
                   2 x 2 x 8 x 16 B at k=4, 4 for yi-6B's); pages of fewer
                   than 65 words share a row
      rows_scale : (Lseg, rows, 1, 128) f32     the f32 scales, K then V,
                   all heads: 128 // (2 * Hkv * hd/8) pages to a row, or
                   whole rows per page
      tail_k/v   : (Lseg, B, 8, Hkv, hd)        raw dtype

    Rows of exactly 128 words keep one byte layout under every tiling the
    TPU compiler picks, so the decode step carries the stacked pool
    through its layer scan, scatters a layer's flushed pages at
    [layer, page rows] and `attend_paged` fetches pages by (layer, page)
    — with no relayout copy of the pool anywhere.  Page-update trees
    (admission, tier spill/restore, prefix verification) keep the plane
    form ``{name}_k/v (Lseg, ..., Hkv) + block_shape``; `page_rows.pack`
    / `unpack` convert bitwise.

    One page = one 8-token block group ACROSS all layers: every layer of a
    slot flushes the same block index at the same step, so page index p in
    segment arrays of every segment belongs to the same logical block.
    `block_table[b, j]` maps slot b's j-th sequence block to its page; the
    engine's host-side free list decides which page that is.  Unmapped
    entries hold 0 (a valid page, so gathers never go out of range) and are
    unreachable: attention masks `kv_pos < flushed` before any gather.
    `n_pages` is static: shared rows do not fix the page count.
    """

    segments: tuple[KVSegment, ...]
    block_table: jax.Array  # (B, S/8) int32
    n_pages: int            # static

    def tree_flatten(self):
        return (self.segments, self.block_table), (self.n_pages,)

    def tree_flatten_with_keys(self):
        ga = jax.tree_util.GetAttrKey
        return ((ga("segments"), self.segments),
                (ga("block_table"), self.block_table)), (self.n_pages,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children[0]), children[1], *aux)

    def replace(self, segments, block_table) -> "PagedKVCache":
        return PagedKVCache(tuple(segments), block_table, self.n_pages)

    @property
    def n_layers(self) -> int:
        return self.segments[-1].stop

    @property
    def max_seq(self) -> int:
        return self.block_table.shape[1] * BLOCK

    @property
    def keeps(self) -> tuple[int, ...]:
        return tuple(s.keep for s in self.segments
                     for _ in range(s.stop - s.start))

    @property
    def codecs(self) -> tuple[str, ...]:
        return tuple(s.codec for s in self.segments
                     for _ in range(s.stop - s.start))

    def page_bytes(self) -> int:
        """Analytic bytes of one page across all layers (the allocation
        granule) — each segment charged by its own codec family."""
        total = 0
        for s in self.segments:
            _, _, _, hkv, hd = s.tail_k.shape
            total += (s.stop - s.start) * \
                block_group_bytes(s.keep, hkv, hd, codec=s.codec)
        return total


def init_paged_cache(cfg, batch: int, max_seq: int, n_pages: int,
                     keep: int = 4, dtype=jnp.bfloat16,
                     plan=None) -> PagedKVCache:
    """Allocate the shared page pool + block tables per `plan`.

    Same per-layer geometry as `init_compressed_cache`, but the block axis
    is a POOL of `n_pages` pages shared by every slot instead of a dense
    (B, max_seq/8) store — the feature-map buffer is sized by the traffic
    you want to hold, not by slots x worst-case depth.
    """
    assert max_seq % BLOCK == 0
    assert n_pages >= 1, n_pages
    hd = cfg.resolved_head_dim
    assert hd % BLOCK == 0, f"head_dim {hd} not 8-tileable"
    plan = plan_lib.as_plan(plan, keep=keep)
    hkv = cfg.n_kv_heads
    segments = []
    for start, stop, pol in plan.segments(cfg.n_layers):
        layout = page_rows.row_layout(pol.codec, pol.kv_keep, hkv, hd)
        planes = page_rows.zeros(layout, stop - start, n_pages)
        for name in TAIL_NAMES:
            planes[name] = jnp.zeros((stop - start, batch, BLOCK, hkv, hd),
                                     dtype)
        segments.append(KVSegment(planes, keep=pol.kv_keep, start=start,
                                  stop=stop, backend=pol.backend,
                                  codec=pol.codec))
    table = jnp.zeros((batch, max_seq // BLOCK), jnp.int32)
    return PagedKVCache(tuple(segments), table, int(n_pages))


def measured_cache_bytes(cache) -> float:
    """MEASURED (data-dependent) compressed bytes resident in the cache —
    what the ROADMAP's "allocate pages by measured, not analytic, size"
    allocates against, reported beside the analytic worst case.

    Variable-length families (bitplane) carry a per-tile length plane
    (``blen``, in bits; written tiles are always > 0) — their measured
    bytes are the exact sum of stored stream bytes plus scale headers.
    Fixed-size families charge their analytic tile bytes per LIVE tile,
    where live is detected from nonzero carrier/scale content (an estimate:
    a flushed tile whose block quantized to all-zeros with zero scale is
    indistinguishable from an unwritten one).  Raw tails are charged at
    their full buffer size.  Host-side accounting — syncs the planes it
    inspects; call from stats paths, not the decode loop.
    """
    def block_bytes(planes, fam, keep):
        total = 0.0
        for sfx in ("_k", "_v"):
            if "blen" + sfx in planes:
                blen = np.asarray(planes["blen" + sfx])
                live = blen > 0
                header = families_lib.SCALE_HEADER_BYTES
                total += float(np.sum(
                    np.where(live, (blen + 7) // 8 + header, 0)))
            else:
                live = np.any(np.asarray(planes["packed" + sfx]) != 0,
                              axis=(-1, -2))
                if "scale" + sfx in planes:
                    live = live | (np.asarray(planes["scale" + sfx]) != 0)
                total += float(np.count_nonzero(live)) * \
                    fam.analytic_tile_bytes(keep)
        return total

    total = 0.0
    for seg in cache.segments:
        planes = seg.as_tree()
        for name in TAIL_NAMES:
            a = planes[name]
            total += int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
        if seg.paged:
            # every page in plane form, one layer at a time: the unpacked
            # copy of a whole pool need not fit beside it on the device
            layout = page_rows.segment_layout(seg)
            ids = jnp.arange(cache.n_pages)
            views = (page_rows.gather_planes(layout, planes, ids,
                                             cache.n_pages, layer)
                     for layer in range(seg.stop - seg.start))
        else:
            views = (planes,)
        total += sum(block_bytes(v, seg.family, seg.keep) for v in views)
    return total


# ---------------------------------------------------------------------------
# Per-layer decode update (operates on the [B, ...] slices for one layer)
# ---------------------------------------------------------------------------

def _tail_write(layer_cache, k_new, v_new, rows, slot):
    # per-row scatters: the row index IS the batch index, so under a
    # slot-sharded pool (data axes on B) every write lands on the shard that
    # owns the slot — constrain the results so GSPMD keeps it that way
    # instead of round-tripping the tail ring through a gather.
    tails = []
    for name, new in (("tail_k", k_new), ("tail_v", v_new)):
        t = layer_cache[name].at[rows, slot].set(
            new[:, 0].astype(layer_cache[name].dtype))
        tails.append(shard_hint(t, "batch", None, "model", None))
    return tails


def _flush_planes(tk, tv, keep, backend, fam) -> dict[str, jax.Array]:
    """Compress each row's full tail ring into one block per row, in the
    family's plane form ``{name}_k/v (B, Hkv) + block_shape``."""
    # (B, 8, Hkv, hd) -> (B, Hkv, 8, hd) planes -> one block per row
    qk, sck = compress_kv_blocks(jnp.swapaxes(tk, 1, 2), keep, backend)
    qv, scv = compress_kv_blocks(jnp.swapaxes(tv, 1, 2), keep, backend)
    # qk: (B, Hkv, 1, hd/8, k, k) -> (B, Hkv, hd/8, k, k); the family lays
    # the quantized blocks out into its declared planes
    upd = {}
    for sfx, q, sc in (("_k", qk, sck), ("_v", qv, scv)):
        for name, plane in fam.pack(q[:, :, 0], sc[:, :, 0], keep).items():
            upd[name + sfx] = plane
    return upd


def update_layer(
    layer_cache: dict[str, jax.Array],
    k_new: jax.Array,  # (B, 1, Hkv, hd)
    v_new: jax.Array,
    pos: jax.Array,    # (B,) per-slot absolute positions (scalar broadcasts)
    keep: int,
    backend: str | None = None,
    *,
    codec: str = "dct",
) -> dict[str, jax.Array]:
    """Write each row's new token into its own tail slot; flush per row
    (the dense store; `update_layer_paged` is the page pool's).

    layer_cache holds the codec family's block planes in cache layout —
    ``{name}_k/v (B, S/8, Hkv) + block_shape`` (dct: packed_k/scale_k/
    packed_v/scale_v) — plus tail_k/tail_v (B, 8, Hkv, hd).

    Every row carries its own position, so the tail write is a batched
    scatter at slot = pos % 8, and the block flush is a masked scatter at
    blk = pos // 8 that only lands for rows whose tail just filled (rows
    that don't flush scatter to an out-of-range index and are dropped).
    A single global cond skips the codec entirely on steps where NO row
    flushes (7 of 8 steps in lock-step serving) — the per-row decision
    stays a masked scatter either way.
    """
    fam = families_lib.get_family(codec)
    b = k_new.shape[0]
    pos = as_pos_vec(pos, b)
    rows = jnp.arange(b)
    slot = jnp.mod(pos, BLOCK)
    tail_k, tail_v = _tail_write(layer_cache, k_new, v_new, rows, slot)

    block_names = tuple(sorted(n for n in layer_cache if n not in TAIL_NAMES))
    blocks = {n: layer_cache[n] for n in block_names}
    ns = layer_cache["packed_k"].shape[1]  # S/8 blocks

    flush_row = slot == BLOCK - 1

    def flush(args):
        blocks, tk, tv = args
        upd = _flush_planes(tk, tv, keep, backend, fam)
        blk = jnp.where(flush_row, pos // BLOCK, ns)  # ns => dropped
        return {n: blocks[n].at[rows, blk].set(
            upd[n].astype(blocks[n].dtype), mode="drop")
            for n in block_names}

    def no_flush(args):
        blocks, _, _ = args
        return dict(blocks)

    blocks = jax.lax.cond(jnp.any(flush_row), flush, no_flush,
                          (blocks, tail_k, tail_v))
    # block-plane layout must MATCH cache_specs: heads on `model` when
    # they divide it, else the S/8 block axis (attn_hint implements that
    # fallback) — a plain heads-only hint would conflict with the pool
    # specs for non-dividing head counts and force a full-store reshard
    # per step
    blocks = {n: attn_hint(a, s_axis=1, h_axis=2) for n, a in blocks.items()}
    return dict(blocks, tail_k=tail_k, tail_v=tail_v)


def update_layer_paged(
    pool: dict[str, jax.Array],   # a segment's stacked rows (Lseg, rows, 1, 128)
    layer: jax.Array,             # () int32 layer index within the segment
    tails: dict[str, jax.Array],  # this layer's tail_k/tail_v (B, 8, Hkv, hd)
    k_new: jax.Array,             # (B, 1, Hkv, hd)
    v_new: jax.Array,
    pos: jax.Array,               # (B,) per-slot positions
    flush_page: jax.Array,        # (B,) page ids; >= n_pages => no flush
    keep: int,
    backend: str | None = None,
    *,
    n_pages: int,
    codec: str = "dct",
) -> tuple[dict[str, jax.Array], dict[str, jax.Array]]:
    """The paged pool's per-layer update: tail write, then flush IN PLACE.

    Every row writes its token into its own tail slot.  Rows whose tail
    just filled (pos % 8 == 7) flush their 8 tokens into page
    `flush_page[b]` of layer `layer` of the stacked row pool.  The codec
    runs under a cond that skips it on steps where no row flushes (7 of 8
    in lock-step serving); it yields small per-row page words, never the
    pool.  The write is unconditional: non-flushing rows carry an
    out-of-range id and write nothing.  On the pallas backend outside a
    mesh the `kernels/page_write` kernel writes the pages' rows at
    [layer, page rows] in place; otherwise one drop-mode XLA scatter does.
    The engine hands out page ids (its free list is the allocator); the
    caller owns the block-table update.  Returns the new pool and the new
    tails.
    """
    fam = families_lib.get_family(codec)
    b = k_new.shape[0]
    pos = as_pos_vec(pos, b)
    slot = jnp.mod(pos, BLOCK)
    tail_k, tail_v = _tail_write(tails, k_new, v_new, jnp.arange(b), slot)
    layout = page_rows.row_layout(codec, keep, tail_k.shape[-2],
                                  tail_k.shape[-1])
    flush_row = slot == BLOCK - 1
    words = jax.lax.cond(
        jnp.any(flush_row),
        lambda: page_rows.pack(layout, _flush_planes(tail_k, tail_v, keep,
                                                     backend, fam)),
        lambda: {g.key: jnp.zeros((b, g.words), g.dtype)
                 for g in layout.groups})
    page = jnp.where(flush_row, flush_page, n_pages)
    tails = dict(tail_k=tail_k, tail_v=tail_v)
    if codec_lib.resolve_backend_name(backend) == "pallas" \
            and ambient_mesh() is None:
        return {g.key: page_rows.write_layer_pages(
            g, pool[g.key], layer, page, words[g.key], n_pages)
            for g in layout.groups}, tails
    # pool layout per cache_specs: pages ride the data axes (the batch
    # scatter crosses banks by design — the page allocator does not know
    # about devices)
    return {g.key: shard_hint(
        page_rows.scatter_pages(g, pool[g.key], page, words[g.key], n_pages,
                                layer), None, "batch", None, None)
        for g in layout.groups}, tails


# ---------------------------------------------------------------------------
# Flash attention over the compressed store (decode: Sq == 1)
# ---------------------------------------------------------------------------

def _repeat_heads(x: jax.Array, n_rep: int) -> jax.Array:
    """(B, Hkv, S, hd) -> (B, Hkv*n_rep, S, hd)."""
    if n_rep == 1:
        return x
    b, hkv, s, hd = x.shape
    return jnp.broadcast_to(x[:, :, None], (b, hkv, n_rep, s, hd)).reshape(b, hkv * n_rep, s, hd)


def attend_compressed(
    q: jax.Array,                 # (B, 1, H, hd)
    layer_cache: dict[str, jax.Array],
    pos: jax.Array,               # (B,) per-slot positions (scalar broadcasts)
    keep: int,
    *,
    kv_block: int = 1024,
    scale: float | None = None,
    backend: str | None = None,
    block_table: jax.Array | None = None,  # (B, S/8) page ids (paged pool)
    codec: str = "dct",
    layer: jax.Array | int = 0,            # paged: layer of the stacked rows
    n_pages: int | None = None,            # paged: pages in the pool
) -> jax.Array:
    """Online-softmax decode attention where K/V history is decompressed per
    chunk INSIDE the scan — compressed bytes are what stream from HBM.

    Each row attends under its OWN causal horizon: packed blocks below that
    row's flushed watermark, plus its raw tail (positions pos-pos%8 .. pos)
    merged with the same running-max algebra.

    The codec family unpacks its declared planes back to quantized blocks
    per chunk (for dct that unpack is the identity, so the op stream is
    bit-for-bit the pre-family path).  With `block_table`, `layer_cache`
    holds the shared PAGE POOL — a segment's stacked row arrays
    (`core.page_rows`, all layers) beside this layer's tails — and each
    chunk gathers its pages at (`layer`, page) through the table and
    unpacks them to the family's planes (an XLA gather and bitcasts).
    Chunk boundaries and every float op after the gather are identical to
    the dense layout, so greedy decode over a paged pool is bitwise the
    dense result (tests pin this).
    """
    fam = families_lib.get_family(codec)
    b, sq, h, hd = q.shape
    pos = as_pos_vec(pos, b)
    hkv = layer_cache["tail_k"].shape[-2]
    k = keep
    if block_table is None:
        bases = tuple(sorted({n[:-2] for n in layer_cache
                              if n not in TAIL_NAMES}))
        nblocks_total = layer_cache["packed_k"].shape[1]
    else:
        layout = page_rows.row_layout(codec, keep, hkv, hd)
        bases = tuple(sorted({n[:-2] for n in layout.plane_names}))
        nblocks_total = block_table.shape[1]
    n_rep = h // hkv
    max_seq = nblocks_total * BLOCK
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    kv_block = min(kv_block, max_seq)
    while max_seq % kv_block:  # shrink to a divisor (max_seq is a mult of 8)
        kv_block -= BLOCK
    assert kv_block % BLOCK == 0 and kv_block > 0
    bpc = kv_block // BLOCK
    nchunks = max_seq // kv_block

    qf = (q.astype(jnp.float32) * scale)[:, 0]           # (B, H, hd)
    flushed = (pos // BLOCK) * BLOCK                      # (B,) packed watermark

    def chunk_body(carry, c):
        m, l, acc = carry
        start = c * bpc
        if block_table is None:
            chunk = {n: jax.lax.dynamic_slice_in_dim(layer_cache[n], start,
                                                     bpc, 1)
                     for n in layer_cache if n not in TAIL_NAMES}
        else:
            # gather this chunk's pages: (B, bpc) table slice -> pool rows.
            # Unmapped entries point at page 0 — valid, and masked below.
            pages = jax.lax.dynamic_slice_in_dim(block_table, start, bpc, 1)
            chunk = page_rows.gather_planes(layout, layer_cache, pages,
                                            n_pages, layer)

        def chunk_planes(sfx):
            # planes per (B, Hkv): (B, nb, Hkv, ...) -> (B, Hkv, nb, ...)
            return {base: jnp.swapaxes(chunk[base + sfx], 1, 2)
                    for base in bases}

        kq, ksc = fam.unpack(chunk_planes("_k"), k)
        vq, vsc = fam.unpack(chunk_planes("_v"), k)
        kc = decompress_kv_blocks(kq, ksc, jnp.float32, backend)
        vc = decompress_kv_blocks(vq, vsc, jnp.float32, backend)
        # kc/vc: (B, Hkv, kv_block, hd)
        kc = attn_hint(kc, s_axis=2, h_axis=1)  # heads else kv_block on model
        vc = attn_hint(vc, s_axis=2, h_axis=1)
        kr = _repeat_heads(kc, n_rep)                     # (B, H, kv_block, hd)
        vr = _repeat_heads(vc, n_rep)
        kv_pos = start * BLOCK + jnp.arange(kv_block)
        valid = kv_pos[None] < flushed[:, None]           # (B, kv_block) per row
        s = jnp.einsum("bhd,bhkd->bhk", qf, kr)
        s = jnp.where(valid[:, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(valid[:, None], jnp.exp(s - m_safe[..., None]), 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhk,bhkd->bhd", p, vr)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h), jnp.float32)
    acc0 = jnp.zeros((b, h, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(chunk_body, (m0, l0, acc0), jnp.arange(nchunks))

    # ---- raw tail: positions flushed .. pos (inclusive) -------------------
    tk = jnp.swapaxes(layer_cache["tail_k"], 1, 2).astype(jnp.float32)  # (B,Hkv,8,hd)
    tv = jnp.swapaxes(layer_cache["tail_v"], 1, 2).astype(jnp.float32)
    tkr = _repeat_heads(tk, n_rep)
    tvr = _repeat_heads(tv, n_rep)
    tail_pos = flushed[:, None] + jnp.arange(BLOCK)       # (B, 8)
    tvalid = tail_pos <= pos[:, None]
    st = jnp.einsum("bhd,bhkd->bhk", qf, tkr)
    st = jnp.where(tvalid[:, None], st, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(st, axis=-1))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    pt = jnp.where(tvalid[:, None], jnp.exp(st - m_safe[..., None]), 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l = l * alpha + jnp.sum(pt, axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum("bhk,bhkd->bhd", pt, tvr)

    out = acc / jnp.maximum(l[..., None], 1e-30)  # (B, H, hd)
    out = shard_hint(out, "batch", "model", None)
    return out[:, None].astype(q.dtype)           # (B, 1, H, hd)


def table_view(block_table: jax.Array,
               attend_blocks: int | None = None) -> jax.Array:
    """Static bucket slice of a block table: its first `attend_blocks`
    entries (None / >= table width => the full table).

    The decode-bucket ladder picks `attend_blocks` to cover the deepest
    live slot's flushed watermark, so every trailing entry this view drops
    can only name blocks the attend masks anyway — the slice is an exact
    no-op on the attention output.  What it changes is cost: the reference
    scan's chunk gather and the paged kernel's grid cover only the sliced
    width, so decode-step work tracks occupied context, not pool capacity.
    """
    nb = block_table.shape[1]
    if attend_blocks is None or attend_blocks >= nb:
        return block_table
    assert attend_blocks >= 1, attend_blocks
    return table_slice_hint(block_table[:, :attend_blocks])


def attend_auto(
    q: jax.Array,
    layer_cache: dict[str, jax.Array],
    pos: jax.Array,               # (B,) per-slot positions (scalar broadcasts)
    keep: int,
    *,
    kv_block: int = 1024,
    backend: str | None = None,
    block_table: jax.Array | None = None,  # (B, nblocks) page ids (paged)
    pages_per_tile: int = 8,
    codec: str = "dct",
    layer: jax.Array | int = 0,       # paged: layer of the stacked rows
    n_pages: int | None = None,       # paged: pages in the pool
) -> jax.Array:
    """Backend-dispatched decode attention over the compressed store.

    `pallas` routes to the fused decompress+attend kernel (int8 blocks are
    what stream from HBM; the IDCT runs in VMEM); `reference` (and any other
    backend) uses the pure-JAX online-softmax scan above. Selection follows
    repro.codec.dispatch, same as the block codec itself. Both backends take
    the per-slot position vector, and both gather paged history through
    `block_table` when given one — possibly a `table_view` bucket slice —
    (the kernel reads the table on the scalar-prefetch path beside `pos`,
    and the paged pool's `layer` after them; `pages_per_tile` is the
    kernel's G-page tile width).

    Only the dct family's plane layout matches what the fused kernel reads
    (`CodecFamily.supports_fused_attend`); other families always decode
    through the reference scan, whatever the backend says.
    """
    pos = as_pos_vec(pos, q.shape[0])
    fused_ok = families_lib.get_family(codec).supports_fused_attend
    if fused_ok and codec_lib.resolve_backend_name(backend) == "pallas":
        from repro.kernels.fused_attend import ops as fa_ops

        return fa_ops.attend_with_tail(q, layer_cache, pos, tile_s=kv_block,
                                       block_table=block_table,
                                       pages_per_tile=pages_per_tile,
                                       keep=keep, layer=layer,
                                       n_pages=n_pages)
    return attend_compressed(q, layer_cache, pos, keep, kv_block=kv_block,
                             backend=backend, block_table=block_table,
                             codec=codec, layer=layer, n_pages=n_pages)


# ---------------------------------------------------------------------------
# Bulk prefill: compress a whole prompt's K/V at once
# ---------------------------------------------------------------------------

def prefill_compress(
    k: jax.Array,  # (B, S, Hkv, hd), S % 8 == 0
    v: jax.Array,
    keep: int,
    pos: jax.Array | None = None,  # (B,) per-row prompt lengths; None => S
    backend: str | None = None,
    codec: str = "dct",
) -> dict[str, jax.Array]:
    """Compress a full prompt's K/V for one layer into cache layout.

    `pos[b]` is row b's prompt length (= its next decode position).  All
    blocks are compressed unconditionally — blocks at or above a row's
    flushed watermark (pos//8 * 8) hold padding garbage, but attention masks
    them (`kv_pos < flushed`) and the decode flush overwrites each one
    before it ever becomes visible.  The trailing partial block of each row
    (positions flushed .. flushed+7) is returned raw as tail_k/tail_v, per
    row, ready to drop into the cache's tail ring.

    Invariant: tail entries at indices >= pos%8 are clamped-gather garbage
    that `tvalid = tail_pos <= pos` treats as valid at position pos itself.
    Decode must therefore WRITE position pos (update_layer) before attending
    at pos — which is exactly what decode_step_compressed does; the first
    post-prefill token is sampled from the prefill logits, never attended
    out of this cache.
    """
    fam = families_lib.get_family(codec)
    b, s = k.shape[:2]
    pos = as_pos_vec(s if pos is None else pos, b)
    kq, ks = compress_kv_blocks(jnp.swapaxes(k, 1, 2), keep, backend)  # (B,Hkv,S/8,hd/8,k,k)
    vq, vs = compress_kv_blocks(jnp.swapaxes(v, 1, 2), keep, backend)
    # per-row raw tail: gather rows flushed .. flushed+7 (clamped; rows past
    # the prompt are masked at attend time by tail_pos <= pos)
    idx = (pos[:, None] // BLOCK) * BLOCK + jnp.arange(BLOCK)  # (B, 8)
    idx = jnp.minimum(idx, s - 1)[:, :, None, None]
    tail_k = jnp.take_along_axis(k, idx, axis=1)               # (B, 8, Hkv, hd)
    tail_v = jnp.take_along_axis(v, idx, axis=1)
    out = dict(tail_k=tail_k, tail_v=tail_v)
    for sfx, q, sc in (("_k", kq, ks), ("_v", vq, vs)):
        for name, plane in fam.pack(q, sc, keep).items():
            out[name + sfx] = jnp.swapaxes(plane, 1, 2)  # -> (B, S/8, Hkv, ...)
    return out


# ---------------------------------------------------------------------------
# Slot lifecycle (continuous batching)
# ---------------------------------------------------------------------------

def cache_reset_slot(cache, slot: jax.Array | int):
    """Zero one batch slot's planes — axis 1 of every leaf (retirement).

    Works on any cache pytree with the (L, B, ...) layout: the
    CompressedKVCache (packed/scale/tail planes; `keep` rides as aux data)
    and the raw k/v and MLA latent dicts alike. Freshly-admitted requests
    overwrite the slot wholesale at prefill, so this is belt-and-braces
    hygiene — but it keeps retired garbage out of storage-stats scans and
    makes slot reuse auditable in tests.
    """
    slot = jnp.asarray(slot, jnp.int32)
    return jax.tree.map(lambda a: a.at[:, slot].set(jnp.zeros_like(a[:, 0])), cache)


def paged_write_slot(cache: PagedKVCache, slot_update, slot: jax.Array,
                     page_ids: jax.Array, table_row: jax.Array) -> PagedKVCache:
    """Splice one admitted request into the paged pool.

    `slot_update` is the per-segment tuple of dicts a paged prefill returns:
    block planes hold the prompt's OWN blocks only in plane form
    ((Lseg, 1, nb, Hkv, ...) with nb = bucket/8 — never max_seq/8), tails
    are the (Lseg, 1, 8, Hkv, hd) raw remainder.  `page_ids` (nb,) carries
    the engine-assigned page per prompt block, padded with out-of-range ids
    (>= P) past the prompt's full blocks so the drop-mode scatter ignores
    the padding blocks; `table_row` (S/8,) is the slot's new block-table
    row (assigned pages then zeros).  Admission therefore writes O(prompt)
    pool rows plus one table row — nothing max_seq-sized is zero-filled.
    """
    slot = jnp.asarray(slot, jnp.int32)
    segments = []
    for seg, upd in zip(cache.segments, slot_update):
        planes = seg.as_tree()
        new = _write_pages(seg, {k: a[:, 0] for k, a in upd.items()},
                           page_ids, cache.n_pages)
        for key in TAIL_NAMES:
            new[key] = jax.lax.dynamic_update_slice_in_dim(
                planes[key], upd[key].astype(planes[key].dtype), slot, axis=1)
        segments.append(seg.replace_arrays(new))
    table = cache.block_table.at[slot].set(table_row)
    return cache.replace(segments, table)


def _write_pages(seg, upd, page_ids, n_pages):
    """Scatter plane-form pages ``(Lseg,) + page_ids.shape + plane`` into a
    segment's row arrays; returns the new row arrays."""
    layout = page_rows.segment_layout(seg)
    ids = jnp.asarray(page_ids, jnp.int32)
    flat = {k: upd[k].reshape((upd[k].shape[0], ids.size)
                              + upd[k].shape[1 + ids.ndim:])
            for k in layout.plane_names}
    return page_rows.scatter_planes(
        layout, {k: seg.planes[k] for k in seg.row_keys}, ids.reshape(-1),
        flat, n_pages)


def paged_write_rows(cache: PagedKVCache, rows_update, slots: jax.Array,
                     page_ids: jax.Array, table_rows: jax.Array) -> PagedKVCache:
    """Splice a PACKED admission (R requests in one bucketed prefill) into
    the paged pool — the batched `paged_write_slot`.

    `rows_update` is the per-segment tuple of dicts a paged prefill returns
    with R rows: block planes in plane form (Lseg, R, nb, Hkv, ...), tails
    (Lseg, R, 8, Hkv, hd).  `slots` (R,) assigns row r to pool slot
    slots[r]; `page_ids` (R, nb) carries each row's engine-assigned page per
    prompt block; `table_rows` (R, S/8) the new block-table rows.  Rows the
    admission group padded to a warmed row count carry out-of-range slot
    ids (>= B) and all-out-of-range page ids, so every one of their writes
    drops — a padding row can land nowhere.  Each segment's pages land in
    its row arrays through one drop-mode scatter per row group.
    """
    slots = jnp.asarray(slots, jnp.int32)
    segments = []
    for seg, upd in zip(cache.segments, rows_update):
        planes = seg.as_tree()
        new = _write_pages(seg, upd, page_ids, cache.n_pages)
        for key in TAIL_NAMES:
            new[key] = planes[key].at[:, slots].set(
                upd[key].astype(planes[key].dtype), mode="drop")
        segments.append(seg.replace_arrays(new))
    table = cache.block_table.at[slots].set(table_rows, mode="drop")
    return cache.replace(segments, table)


def paged_gather_slot(cache: PagedKVCache, slot: jax.Array,
                      page_ids: jax.Array):
    """Read pages (+ one slot's tail) OUT of the pool — the gather half of
    the tier path, exact inverse of `paged_write_slot`.

    Returns the per-segment tuple of dicts `paged_write_slot` accepts:
    block planes in plane form (Lseg, 1, nb, Hkv, ...) read from the rows
    at `page_ids` (nb,) and unpacked, tails (Lseg, 1, 8, Hkv, hd) sliced at
    `slot`. Out-of-range page ids clamp to the pool — callers pad the page
    vector to a warmed bucket width and ignore the padding entries,
    mirroring the drop-mode scatter on the write side. The engine's
    TierManager numpy-ifies the result into host pages; feeding it back
    through `paged_write_slot` at fresh page ids is a bitwise round trip
    (the unpack is bitcasts; int8/f32/raw-tail planes copy exactly).

    Tier semantics: which of a slot's logical blocks are device- vs
    host-resident is HOST state (the engine's per-slot page lists and
    parked records) — the device block table only ever holds device page
    ids, and a parked slot's row is zeroed until its restore rebuilds it.
    """
    slot = jnp.asarray(slot, jnp.int32)
    out = []
    for seg in cache.segments:
        planes = seg.as_tree()
        got = page_rows.gather_planes(page_rows.segment_layout(seg), planes,
                                      page_ids, cache.n_pages)
        upd = {key: a[:, None] for key, a in got.items()}  # (Lseg, 1, nb, ...)
        for key in TAIL_NAMES:
            upd[key] = jax.lax.dynamic_slice_in_dim(planes[key], slot, 1,
                                                    axis=1)
        out.append(upd)
    return out


def paged_rows_match(cache: PagedKVCache, rows_update, page_ids: jax.Array):
    """Bitwise-compare pool pages against admission update rows.

    `rows_update` is the plane-form (Lseg, R, nb, ...) tree a packed paged
    prefill returns; `page_ids` (R, nb) names the candidate page per (row,
    block).  Returns an (R, nb) bool: True iff every packed int8 element
    AND every f32 scale of the candidate page, read from the rows and
    unpacked, equals the row's freshly computed block — the copy-on-write
    sharing verifier (hash-equal prefixes are only shared once this says
    their pages are bitwise equal). Out-of-range ids clamp; callers mask
    non-candidate entries host-side.
    """
    ok = jnp.ones(page_ids.shape, bool)
    for seg, upd in zip(cache.segments, rows_update):
        got = page_rows.gather_planes(page_rows.segment_layout(seg),
                                      seg.as_tree(), page_ids, cache.n_pages)
        for key in seg.page_keys:
            want = upd[key].astype(got[key].dtype)
            eq = got[key] == want                 # (Lseg, R, nb, ...)
            axes = tuple(a for a in range(eq.ndim) if a not in (1, 2))
            ok = ok & jnp.all(eq, axis=axes)
    return ok


def paged_reset_slot(cache: PagedKVCache, slot: jax.Array) -> PagedKVCache:
    """Retire one slot: zero its tails and block-table row.

    Page CONTENTS are not touched — the engine's free list reclaims the
    page ids, and a page is unreachable the moment no table row maps it
    (the device-side analogue of free()).  Zeroing the table row keeps
    retired mappings out of gathers and makes reuse auditable in tests.
    """
    slot = jnp.asarray(slot, jnp.int32)
    segments = []
    for seg in cache.segments:
        planes = seg.as_tree()
        new = dict(planes)
        for key in TAIL_NAMES:
            new[key] = planes[key].at[:, slot].set(
                jnp.zeros_like(planes[key][:, 0]))
        segments.append(seg.replace_arrays(new))
    table = cache.block_table.at[slot].set(
        jnp.zeros_like(cache.block_table[0]))
    return cache.replace(segments, table)
