"""Row storage of the paged KV pool: every page plane as rows of 128 words.

A paged segment keeps its codec family's block planes (`families.PlaneSpec`)
not in their own shapes but as ROWS of exactly 128 32-bit words, one array
per row group, shape ``(Lseg, rows, 1, 128)``.  A row of 128 words is byte
identical under every tiling the TPU compiler picks for it, so the stacked
pool can ride a layer scan as its carry, take a layer's flushed pages and
feed the attend kernel without a single relayout copy; any other minor
shape gets one somewhere (a (hd/8, k, k) int8 corner sits on a (32, 128)
tile).

Row groups, derived from the family's declared planes (no per-model knob):

  * ``rows_codes`` (int32): every sub-word plane (dct's int8 corners, asc's
    exponent bytes, bitplane's mask bytes) of one page and layer, K then V,
    all KV heads, bit-packed 4 bytes to a word.  A plane's last axis is
    zero-padded to whole words and its word index placed before the
    second-to-last axis: the dct corner (hd/8, k, k) becomes words
    ``[j, c, u]`` holding ``q[j, u, 4c .. 4c+3]`` (byte i = bits 8i..8i+7),
    so every corner row u of a k <= 4 block is one word and a block's words
    are contiguous.  Such a run of words (a UNIT: one minor 2-D block, here
    one (head, block) corner of k * ceil(k/4) words) never straddles two
    rows of a page: a unit that would cross a row's end starts the next
    row, and the words it skips stay zero (keep 3 at head_dim 128 with 4
    KV heads: 42 corners a row; keep 6: 10).
  * ``rows_<name>`` (the plane's own 32-bit dtype): each 32-bit plane (the
    f32 scales, bitplane's int32 lengths), K then V, all heads, C order.

A group whose page holds ``words <= 64`` shares each row between
``128 // words`` pages (page p at lane ``(p % ppr) * words`` of row
``p // ppr``); a larger one gives each page ``ceil(words / 128)`` rows of
its own, the last padded.  Pages are addressed by id only: which page a
slot's block lives in is the engine's block table, as before.

`pack` / `unpack` convert between the family's plane form ``(..., Hkv) +
block_shape`` per ``{name}_k`` / ``{name}_v`` and per-page words
``(..., words)`` per group, bitwise both ways; `scatter_pages` /
`gather_pages` move per-page words into and out of the row arrays (XLA),
and `write_layer_pages` writes one layer's pages in place through the
`kernels/page_write` kernel.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.codec import families as families_lib
from repro.kernels.page_write.kernel import write_rows

ROW_WORDS = 128
ROW_PREFIX = "rows_"
CODES = ROW_PREFIX + "codes"


def _words_of(block_shape: tuple[int, ...], itemsize: int) -> int:
    """Words one (head, K or V) block plane takes in its row group."""
    if itemsize == 4:
        return int(np.prod(block_shape, dtype=np.int64))
    assert itemsize == 1, itemsize
    lead, last = block_shape[:-1], block_shape[-1]
    return int(np.prod(lead, dtype=np.int64)) * -(-last // 4)


def _unit_words(shape: tuple[int, ...]) -> int:
    """Words of one unit of an 8-bit plane ``(Hkv,) + block``: its minor
    2-D block (``_bytes_to_words`` keeps one contiguous) or one word."""
    return -(-shape[-1] // 4) * shape[-2] if len(shape) >= 3 else 1


def _place(units: list[tuple[int, int]]) -> tuple[int, ...] | None:
    """Stored word of each packed word, given (count, words) runs of units:
    units go one after another, but one that would cross a row's end
    starts the next row.  None when nothing moves."""
    placed, off = [], 0
    for count, unit in units:
        for _ in range(count):
            if unit <= ROW_WORDS and off % ROW_WORDS + unit > ROW_WORDS:
                off = -(-off // ROW_WORDS) * ROW_WORDS
            placed.extend(range(off, off + unit))
            off += unit
    return None if placed[-1] == len(placed) - 1 else tuple(placed)


@dataclass(frozen=True)
class RowGroup:
    """One stored row array: its planes, words per page and placement."""

    key: str                                 # storage name in the segment
    dtype: object                            # int32 / float32
    planes: tuple[tuple[str, object, tuple[int, ...]], ...]  # (name, dtype, (Hkv,)+block)
    words: int                               # words of one page and layer
    placed: tuple[int, ...] | None = None    # stored word of each packed word
                                             # (None: the same word)

    @property
    def pages_per_row(self) -> int:
        return ROW_WORDS // self.words if self.words <= ROW_WORDS // 2 else 1

    @property
    def rows_per_page(self) -> int:
        return -(-self.words // ROW_WORDS) if self.pages_per_row == 1 else 1

    @property
    def window(self) -> int:
        """Words one page's update carries: its whole rows, or its slot."""
        return self.words if self.pages_per_row > 1 else \
            self.rows_per_page * ROW_WORDS

    def n_rows(self, n_pages: int) -> int:
        if self.pages_per_row > 1:
            return -(-n_pages // self.pages_per_row)
        return n_pages * self.rows_per_page

    def locate(self, page: jax.Array) -> tuple[jax.Array, jax.Array]:
        """(first row, first lane) of `page`'s words."""
        ppr = self.pages_per_row
        if ppr > 1:
            return page // ppr, (page % ppr) * self.words
        return page * self.rows_per_page, jnp.zeros_like(page)


@dataclass(frozen=True)
class RowLayout:
    """Every row group of one segment (a codec family at keep, Hkv, hd)."""

    groups: tuple[RowGroup, ...]
    codec: str
    keep: int
    n_kv_heads: int
    head_dim: int

    def group(self, key: str) -> RowGroup:
        return next(g for g in self.groups if g.key == key)

    @property
    def plane_names(self) -> tuple[str, ...]:
        """The plane-form names (``{name}_k`` / ``{name}_v``) of a page."""
        return tuple(sorted(n for g in self.groups for n, _, _ in g.planes))

    def plane_shapes(self) -> dict[str, tuple[tuple[int, ...], object]]:
        """Plane-form ``(Hkv,) + block_shape`` and dtype per plane name."""
        return {n: (shape, dt) for g in self.groups for n, dt, shape in g.planes}

    def shapes(self, n_layers: int, n_pages: int) -> dict[str, tuple]:
        return {g.key: ((n_layers, g.n_rows(n_pages), 1, ROW_WORDS), g.dtype)
                for g in self.groups}

    def corner_word(self, n: int) -> int:
        """Stored word of dct corner `n` (``(kv * Hkv + head) * hd/8 +
        block``) in a page's `rows_codes`: it and its k * ceil(k/4) words
        lie in one row."""
        g = self.group(CODES)
        w = n * self.keep * -(-self.keep // 4)
        return w if g.placed is None else g.placed[w]


def row_layout(codec: str, keep: int, n_kv_heads: int,
               head_dim: int) -> RowLayout:
    """The row groups of a segment, from its family's declared planes."""
    fam = families_lib.get_family(codec)
    specs = fam.plane_specs(keep, head_dim)
    groups = []
    byte_planes, units = [], []
    for sfx in ("_k", "_v"):
        for s in specs:
            if jnp.dtype(s.dtype).itemsize == 1:
                shape = (n_kv_heads,) + s.block_shape
                byte_planes.append((s.name + sfx, s.dtype, shape))
                unit = _unit_words(shape)
                units.append((n_kv_heads * _words_of(s.block_shape, 1)
                              // unit, unit))
    if byte_planes:
        placed = _place(units)
        words = sum(c * u for c, u in units) if placed is None \
            else placed[-1] + 1
        groups.append(RowGroup(CODES, jnp.int32, tuple(byte_planes), words,
                               placed))
    for s in specs:
        if jnp.dtype(s.dtype).itemsize == 4:
            planes = tuple((s.name + sfx, s.dtype,
                            (n_kv_heads,) + s.block_shape)
                           for sfx in ("_k", "_v"))
            groups.append(RowGroup(ROW_PREFIX + s.name, s.dtype, planes,
                                   2 * n_kv_heads * _words_of(s.block_shape, 4)))
        else:
            assert jnp.dtype(s.dtype).itemsize == 1, (s.name, s.dtype)
    return RowLayout(tuple(groups), codec, keep, n_kv_heads, head_dim)


def segment_layout(seg) -> RowLayout:
    """A paged segment's layout; Hkv and hd come off its tail ring."""
    tail = seg.planes["tail_k"]
    return row_layout(seg.codec, seg.keep, tail.shape[-2], tail.shape[-1])


# ---------------------------------------------------------------------------
# plane form <-> per-page words
# ---------------------------------------------------------------------------

def _bytes_to_words(x: jax.Array, ndim: int) -> jax.Array:
    """(..., *shape) 8-bit -> (..., words) int32 per the module's order."""
    lead = x.shape[:x.ndim - ndim]
    x = jax.lax.bitcast_convert_type(x, jnp.int8) if x.dtype != jnp.int8 else x
    last = x.shape[-1]
    cw = -(-last // 4)
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, 4 * cw - last)])
    x = x.reshape(x.shape[:-1] + (cw, 4))
    if ndim >= 3:       # a block of rank >= 2: (..., a, cw, 4) -> (..., cw, a, 4)
        x = jnp.swapaxes(x, -3, -2)
    w = jax.lax.bitcast_convert_type(x, jnp.int32)
    return w.reshape(lead + (-1,))


def _words_to_bytes(w: jax.Array, shape: tuple[int, ...], dtype) -> jax.Array:
    """Inverse of `_bytes_to_words`: (..., words) -> (..., *shape)."""
    lead = w.shape[:-1]
    last = shape[-1]
    cw = -(-last // 4)
    if len(shape) >= 3:
        w = w.reshape(lead + shape[:-2] + (cw, shape[-2]))
        x = jax.lax.bitcast_convert_type(w, jnp.int8)    # (..., cw, a, 4)
        x = jnp.swapaxes(x, -3, -2)                      # (..., a, cw, 4)
    else:
        w = w.reshape(lead + shape[:-1] + (cw,))
        x = jax.lax.bitcast_convert_type(w, jnp.int8)    # (..., cw, 4)
    x = x.reshape(x.shape[:-2] + (4 * cw,))[..., :last]
    return x if jnp.dtype(dtype) == jnp.int8 else \
        jax.lax.bitcast_convert_type(x, dtype)


def pack(layout: RowLayout, planes: dict[str, jax.Array]) -> dict[str, jax.Array]:
    """Plane form ``(..., Hkv) + block`` -> per-page words ``(..., words)``."""
    out = {}
    for g in layout.groups:
        parts = []
        for name, dt, shape in g.planes:
            a = planes[name].astype(dt)
            if jnp.dtype(dt).itemsize == 1:
                parts.append(_bytes_to_words(a, len(shape)))
            else:
                parts.append(a.reshape(a.shape[:a.ndim - len(shape)] + (-1,)))
        w = jnp.concatenate(parts, axis=-1) if len(parts) > 1 else parts[0]
        if g.placed is not None:      # skipped words read the zero at the end
            src = np.full(g.words, len(g.placed), np.int32)
            src[np.asarray(g.placed)] = np.arange(len(g.placed))
            w = jnp.concatenate([w, jnp.zeros_like(w[..., :1])], axis=-1)
            w = jnp.take(w, jnp.asarray(src), axis=-1)
        out[g.key] = w
    return out


def unpack(layout: RowLayout, words: dict[str, jax.Array]) -> dict[str, jax.Array]:
    """Per-page words -> plane form; the exact inverse of `pack`."""
    out = {}
    for g in layout.groups:
        w = words[g.key]
        if g.placed is not None:
            w = jnp.take(w, jnp.asarray(g.placed, np.int32), axis=-1)
        off = 0
        for name, dt, shape in g.planes:
            if jnp.dtype(dt).itemsize == 1:
                n = int(np.prod(shape[:-1])) * -(-shape[-1] // 4)
                out[name] = _words_to_bytes(w[..., off:off + n], shape, dt)
            else:
                n = int(np.prod(shape))
                out[name] = w[..., off:off + n].reshape(w.shape[:-1] + shape)
            off += n
    return out


# ---------------------------------------------------------------------------
# per-page words <-> row arrays
# ---------------------------------------------------------------------------

def scatter_pages(g: RowGroup, arr: jax.Array, pages: jax.Array,
                  words: jax.Array, n_pages: int,
                  layer: jax.Array | int | None = None) -> jax.Array:
    """Write pages' words into a row array ``(L, rows, 1, 128)``.

    `pages` (N,) ids; ids outside [0, n_pages) are dropped.  With `layer`,
    `words` is (N, words) and lands in that one layer; without, it is
    (L, N, words) and lands in every layer.  One windowed scatter: a page
    owning whole rows writes them, a page sharing a row writes its slot.
    """
    n_rows = arr.shape[1]
    pages = jnp.asarray(pages, jnp.int32)
    ok = (pages >= 0) & (pages < n_pages)
    row, lane = g.locate(jnp.where(ok, pages, 0))
    row = jnp.where(ok, row, n_rows)              # out of range: dropped
    pad = g.window - g.words
    if pad:
        words = jnp.pad(words, [(0, 0)] * (words.ndim - 1) + [(0, pad)])
    rw = g.rows_per_page if g.pages_per_row == 1 else 1
    lw = g.window if g.pages_per_row > 1 else ROW_WORDS
    if layer is None:
        n_l = arr.shape[0]
        upd = jnp.moveaxis(words, 0, 1).reshape(pages.shape[0], n_l, rw, 1, lw)
        lay = jnp.zeros_like(row)
    else:
        n_l = 1
        upd = words.reshape(pages.shape[0], 1, rw, 1, lw)
        lay = jnp.broadcast_to(jnp.asarray(layer, jnp.int32), row.shape)
    idx = jnp.stack([lay, row, jnp.zeros_like(row), lane], axis=-1)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2, 3, 4), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0, 1, 2, 3))
    return jax.lax.scatter(arr, idx, upd.astype(arr.dtype), dnums,
                           indices_are_sorted=False, unique_indices=False,
                           mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def write_layer_pages(g: RowGroup, arr: jax.Array, layer: jax.Array,
                      pages: jax.Array, words: jax.Array, n_pages: int,
                      interpret: bool | None = None) -> jax.Array:
    """`scatter_pages` of (N, words) into one `layer`, as the
    `kernels/page_write` kernel: in place, one DMA per written page.

    Pages that share a row are merged here first: each touched row gets
    the lanes of every page written into it, so the kernel's
    read-modify-write of a row is the same whichever update lands last."""
    pages = jnp.asarray(pages, jnp.int32)
    ok = (pages >= 0) & (pages < n_pages)
    row, lane = g.locate(jnp.where(ok, pages, 0))
    rows = jnp.where(ok, row, -1)
    bits = jax.lax.bitcast_convert_type(words.astype(arr.dtype), jnp.int32)
    if g.pages_per_row == 1:
        pad = g.rows_per_page * ROW_WORDS - g.words
        bits = jnp.pad(bits, ((0, 0), (0, pad)))
        upd = bits.reshape(-1, g.rows_per_page, 1, ROW_WORDS)
        return write_rows(arr, layer, rows,
                          jax.lax.bitcast_convert_type(upd, arr.dtype),
                          interpret=interpret)
    rel = jnp.arange(ROW_WORDS)[None] - lane[:, None]          # (N, 128)
    inside = (rel >= 0) & (rel < g.words) & ok[:, None]
    placed = jnp.where(inside, jnp.take_along_axis(
        bits, jnp.clip(rel, 0, g.words - 1), axis=1), 0)
    same = (row[:, None] == row[None, :])[..., None]           # (N, N, 1)
    upd = jnp.bitwise_or.reduce(jnp.where(same, placed[None], 0), axis=1)
    mask = jnp.any(same & inside[None], axis=1).astype(jnp.int32)
    return write_rows(arr, layer, rows,
                      jax.lax.bitcast_convert_type(upd, arr.dtype)
                      .reshape(-1, 1, 1, ROW_WORDS),
                      mask.reshape(-1, 1, 1, ROW_WORDS), interpret=interpret)


def gather_pages(g: RowGroup, arr: jax.Array, pages: jax.Array,
                 n_pages: int, layer: jax.Array | int | None = None) -> jax.Array:
    """Read pages' words out of a row array: ``pages.shape + (words,)``
    for one `layer`, ``(L,) + pages.shape + (words,)`` without.  Ids clamp
    into the pool (callers mask what they did not map)."""
    pages = jnp.clip(jnp.asarray(pages, jnp.int32), 0, n_pages - 1)
    row, lane = g.locate(pages)
    src = arr[:, :, 0] if layer is None else \
        jax.lax.dynamic_index_in_dim(arr, layer, 0, keepdims=False)[:, 0]
    if g.pages_per_row > 1:
        cols = lane[..., None] + jnp.arange(g.words)
        rows = jnp.broadcast_to(row[..., None], cols.shape)
        return src[..., rows, cols]
    rows = row[..., None] + jnp.arange(g.rows_per_page)
    w = src[..., rows, :]                                  # (..., R, 128)
    return w.reshape(w.shape[:-2] + (-1,))[..., :g.words]


def gather_planes(layout: RowLayout, rows: dict[str, jax.Array],
                  pages: jax.Array, n_pages: int,
                  layer: jax.Array | int | None = None) -> dict[str, jax.Array]:
    """Plane form of pages read from the row arrays (see `gather_pages`)."""
    return unpack(layout, {g.key: gather_pages(g, rows[g.key], pages, n_pages,
                                               layer)
                           for g in layout.groups})


def scatter_planes(layout: RowLayout, rows: dict[str, jax.Array],
                   pages: jax.Array, planes: dict[str, jax.Array],
                   n_pages: int,
                   layer: jax.Array | int | None = None) -> dict[str, jax.Array]:
    """Write plane-form pages into the row arrays (see `scatter_pages`)."""
    words = pack(layout, planes)
    return {g.key: scatter_pages(g, rows[g.key], pages, words[g.key],
                                 n_pages, layer)
            for g in layout.groups}


def zeros(layout: RowLayout, n_layers: int, n_pages: int) -> dict[str, jax.Array]:
    return {k: jnp.zeros(shape, dt)
            for k, (shape, dt) in layout.shapes(n_layers, n_pages).items()}


def is_row_key(name: str) -> bool:
    return name.startswith(ROW_PREFIX)


__all__ = ["ROW_WORDS", "CODES", "RowGroup", "RowLayout", "row_layout",
           "segment_layout", "pack", "unpack", "scatter_pages",
           "gather_pages", "gather_planes", "scatter_planes", "zeros",
           "is_row_key"]
