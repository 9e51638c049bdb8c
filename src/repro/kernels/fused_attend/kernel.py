"""Pallas TPU kernel: fused DCT-decompress + decode attention.

The dry-run measurement (EXPERIMENTS.md §Perf, yi-6b decode_32k) shows why
this kernel must exist: in pure XLA the compressed KV store DECOMPRESSES to
a full-size bf16 K/V in HBM before attention reads it — ~73 MB/layer of
traffic vs 34 MB raw, i.e. compression LOSES without fusion. This kernel is
the paper's architecture transplanted to TPU: compressed blocks stream from
HBM (int8, (k*k+4)/128 of bf16 bytes), the IDCT runs in VMEM as two skinny
constant matmuls, and the attention consumes K/V tiles that never exist in
HBM — the analogue of the paper's IDCT feeding the PE array "in one
computing stream".

Dense plane kernel (attend_compressed_plane), per (batch, kv-head) plane:
  packed_k/v : (S/8, hd/8, k, k) int8     scale_k/v : (S/8, hd/8) f32
  q          : (H, hd) — the n_rep query heads sharing this kv head
  out        : (H, hd) f32 — attention over the FLUSHED history
               (< pos//8*8; the raw 8-token tail is merged by ops.py with
               the same online-softmax algebra)
  Grid: (S / TILE_S,) sequence tiles; the online-softmax running state
  (m, l, acc) lives in VMEM scratch carried across sequentially-executed
  grid steps.

Paged pool kernel (attend_paged), all planes in one explicit grid:
  Grid: (B, nblocks / G) — each grid step fetches G pages by (layer, page)
  from the segment's STACKED row pool (`core.page_rows`; page ids and the
  layer ride the scalar-prefetch path, so every page DMA is issued from
  SMEM-resident entries): one DMA brings a page's int8 codes for K, V and
  every KV head, one its f32 scales.  Per KV head it scores and attends the
  G pages in the DCT domain with (n_rep, G*8) score / PV products.
  A tile whose first position is at or past the slot's flushed watermark
  skips its decompress + matmuls entirely under `pl.when` (skipped tiles
  contribute exactly nothing to the online-softmax state, so the output is
  unchanged).  The finalize step merges the raw 8-token tail ring with the
  same online-softmax algebra and NORMALIZES, so one pallas_call emits the
  finished attention output — no separate XLA tail pass.  `nblocks` is
  whatever table width the caller hands in: the serve engine slices the
  table to a decode-ladder bucket covering the deepest live context
  (core.kv_cache.table_view), so the grid tracks occupancy, not pool
  capacity.

VMEM per grid step stays far inside the ~16 MB budget for every supported
geometry (see the README kernel section for the per-(G, keep, hd) table);
the dominant term is the two decompressed f32 tiles, 2 * G*8 * hd * 4 B.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import page_rows
from repro.core.dct import _dct_matrix_np
from repro.kernels import resolve_interpret

BLOCK = 8


def _dct_k_np(keep: int) -> np.ndarray:
    return _dct_matrix_np(BLOCK)[:keep].astype(np.float32)


def fit_tile(requested: int, total: int, unit: int = BLOCK) -> int:
    """Largest multiple of `unit` dividing `total`, capped at `requested`.

    The explicit tile-shrink rule shared by the sequence tiling (unit=8
    tokens) and the page tiling (unit=1 page): the result is asserted to be
    a unit multiple that divides `total` exactly — never a silent shrink to
    a non-aligned width."""
    assert total >= unit and total % unit == 0, (total, unit)
    t = max(min(requested - requested % unit, total), unit)
    while total % t:
        t -= unit
    assert unit <= t <= total and total % t == 0 and t % unit == 0, \
        (requested, total, unit, t)
    return t


def _attend_kernel(
    pos_ref,                    # scalar prefetch: () int32
    pk_ref, sk_ref, pv_ref, sv_ref, q_ref, ck_ref,
    o_ref,
    m_ref, l_ref, acc_ref,      # VMEM scratch (carried)
    *, tile_s: int, scale: float,
):
    ts8 = tile_s // BLOCK
    step = pl.program_id(0)
    ck = ck_ref[...]                           # (k, 8) DCT constant (VMEM)

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def dec(p_ref, s_ref):
        """int8 tile -> f32 (tile_s, hd): per-8x8-block z -> Ck^T z Ck."""
        z = p_ref[...].astype(jnp.float32) * s_ref[...][..., None, None]
        t = jnp.einsum("ua,ijuv,vb->ijab", ck, z, ck)   # (ts8, nh, 8, 8)
        t = jnp.swapaxes(t, 1, 2)                       # (ts8, 8, nh, 8)
        return t.reshape(ts8 * BLOCK, -1)

    kt = dec(pk_ref, sk_ref)
    vt = dec(pv_ref, sv_ref)

    q = q_ref[...].astype(jnp.float32) * scale          # (H, hd)
    s = jax.lax.dot(q, kt.T, preferred_element_type=jnp.float32)  # (H, tile_s)
    kv_pos = step * tile_s + jax.lax.broadcasted_iota(jnp.int32, (1, tile_s), 1)
    valid = kv_pos < (pos_ref[0] // BLOCK) * BLOCK      # flushed blocks only
    s = jnp.where(valid, s, -jnp.inf)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, vt, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(step == pl.num_programs(0) - 1)
    def _finalize():
        # emit un-normalized stats so the caller can merge the raw tail
        o_ref[0] = acc_ref[...]
        o_ref[1] = jnp.broadcast_to(m_ref[...], acc_ref.shape)
        o_ref[2] = jnp.broadcast_to(l_ref[...], acc_ref.shape)


def attend_compressed_plane(
    packed_k: jax.Array,   # (S/8, hd/8, k, k) int8
    scale_k: jax.Array,    # (S/8, hd/8) f32
    packed_v: jax.Array,
    scale_v: jax.Array,
    q: jax.Array,          # (H, hd)
    pos: jax.Array,        # () int32
    *,
    tile_s: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused decompress+attend over one (batch, kv-head) plane.

    Returns (acc (H, hd), m (H, hd) broadcast, l (H, hd) broadcast) —
    un-normalized online-softmax stats over the flushed history, ready for
    tail merging. out = acc / l after merging. interpret=None auto-selects
    via the codec dispatch rules (compiled on TPU, interpret elsewhere).
    """
    interpret = resolve_interpret(interpret)
    ns, nh, k, _ = packed_k.shape
    s_total = ns * BLOCK
    hd = nh * BLOCK
    h = q.shape[0]
    tile_s = fit_tile(tile_s, s_total)
    ts8 = tile_s // BLOCK

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s_total // tile_s,),
        in_specs=[
            pl.BlockSpec((ts8, nh, k, k), lambda i, pos: (i, 0, 0, 0)),
            pl.BlockSpec((ts8, nh), lambda i, pos: (i, 0)),
            pl.BlockSpec((ts8, nh, k, k), lambda i, pos: (i, 0, 0, 0)),
            pl.BlockSpec((ts8, nh), lambda i, pos: (i, 0)),
            pl.BlockSpec((h, hd), lambda i, pos: (0, 0)),
            pl.BlockSpec((k, BLOCK), lambda i, pos: (0, 0)),
        ],
        out_specs=pl.BlockSpec((3, h, hd), lambda i, pos: (0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),   # m
            pltpu.VMEM((h, 1), jnp.float32),   # l
            pltpu.VMEM((h, hd), jnp.float32),  # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_attend_kernel, tile_s=tile_s,
                          scale=1.0 / float(np.sqrt(hd))),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((3, h, hd), jnp.float32),
        interpret=interpret,
    )(pos.reshape(1), packed_k, scale_k, packed_v, scale_v, q,
      jnp.asarray(_dct_k_np(k)))
    acc, m_b, l_b = out[0], out[1], out[2]
    return acc, m_b[:, :1], l_b[:, :1]


# ---------------------------------------------------------------------------
# Paged pool: gather history through the block table (scalar prefetch)
# ---------------------------------------------------------------------------

_HI = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jax.lax.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a @ b.T without materializing the transpose."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _paged_consts_np(keep: int, nh: int, g_pages: int):
    """Constant IDCT placements for the paged kernel.

    ckg (G, k, G*8): ckg[g, u, g*8 + a] = C8[u, a] — row-side IDCT of page g
        landing at its own 8 sequence columns of the score tile.
    bde (nh, k, hd): bde[j, v, j*8 + b] = C8[v, b] — column-side IDCT of
        head-dim block j landing at its own 8 lanes of the output.
    """
    ck = _dct_k_np(keep)
    ckg = np.zeros((g_pages, keep, g_pages * BLOCK), np.float32)
    for g in range(g_pages):
        ckg[g, :, g * BLOCK:(g + 1) * BLOCK] = ck
    bde = np.zeros((nh, keep, nh * BLOCK), np.float32)
    for j in range(nh):
        bde[j, :, j * BLOCK:(j + 1) * BLOCK] = ck
    return ckg, bde


def _scaled(c, x):
    """Scalar c times array x, as `c * x` traces it but in fewer Python
    steps: the kernel body holds thousands of these."""
    return jax.lax.mul(c, x)


def _byte_rows(w, keep: int):
    """One stored row (1, 128) int32 -> (keep, 128) f32: row v holds, at
    lane L, corner column v of the word run starting at L — byte v % 4 of
    word L + (v // 4) * keep, sign-extended (shifts and masks only)."""
    wb = jnp.broadcast_to(w, (4, w.shape[-1]))
    sh = 24 - 8 * jax.lax.broadcasted_iota(jnp.int32, wb.shape, 0)
    e = jax.lax.shift_right_arithmetic(jax.lax.shift_left(wb, sh),
                                       jnp.int32(24)).astype(jnp.float32)
    parts = [e if c == 0 else pltpu.roll(e, e.shape[-1] - c * keep, 1)
             for c in range(-(-keep // 4))]
    e = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return e[:keep]


# The paged kernel's tile body is traced through module-level jits: a jit
# inside a Pallas kernel lowers inline (the same ops in the same order), and
# its trace is kept, so the four decode-ladder buckets' kernels -- and the
# pages and heads inside one -- share a handful of small traces instead of
# each tracing thousands of ops.

def _corner(rows, word: int, keep: int):
    """(k, k) f32 corner, [v, u] = q[u, v], starting at stored `word`."""
    r, lane = divmod(word, 128)
    x = rows[r]
    if lane:
        x = pltpu.roll(x, 128 - lane, 1)
    return jax.lax.slice(x, (0, 0), (keep, keep))


def _scale(scale_ref, s_off, n: int):
    """The f32 scale of corner `n` of a page, from SMEM."""
    r, lane = divmod(n, 128)
    return scale_ref[r, 0, lane if s_off is None else s_off + lane]


@functools.partial(jax.jit, static_argnames=("keep", "ppr", "words"))
def _page_rows(code_ref, page, *, keep: int, ppr: int, words: int):
    """A page's stored code rows unpacked once for every head: per row a
    (k, 128) f32 plane whose lane L holds the corner column starting at
    word L (its slot of a shared row first rolled to lane 0)."""
    rows = []
    for r in range(code_ref.shape[0]):
        w = code_ref[r]                                        # (1, 128)
        if ppr > 1:
            w = pltpu.roll(w, (128 - (page % ppr) * words) % 128, 1)
        rows.append(_byte_rows(w, keep))
    return rows


@functools.partial(jax.jit, static_argnames=("keep", "corners", "first"))
def _page_scores(qh, rows, scale_ref, s_off, ckg_g, *, keep: int,
                 corners: tuple[int, ...], first: int):
    """One page's scores for one head in the DCT domain: per head-dim block
    j one (n_rep, k) x (k, k) product scaled by the block's f32 scale, then
    the row-side IDCT places the page's 8 positions in the (n_rep, G*8)
    score tile.  `corners` are the head's K corners' words, `first` their
    first corner number."""
    w = _scaled(_scale(scale_ref, s_off, first),
                _dot(qh[0], _corner(rows, corners[0], keep)))
    for j in range(1, len(qh)):
        w = jax.lax.add(w, _scaled(_scale(scale_ref, s_off, first + j), _dot(
            qh[j], _corner(rows, corners[j], keep))))
    return _dot(w, ckg_g)                                       # (n_rep, G*8)


@functools.partial(jax.jit, static_argnames=("keep", "corners", "first"))
def _page_values(ph, rows, scale_ref, s_off, *, keep: int,
                 corners: tuple[int, ...], first: int):
    """One page's P @ V terms for one head: per block j the scaled (k, k)
    page block times ph (P with the row-side IDCT folded in)."""
    return [_scaled(_scale(scale_ref, s_off, first + j),
                    _dot_nt(ph, _corner(rows, c, keep)))
            for j, c in enumerate(corners)]


@functools.partial(jax.jit, static_argnames=("keep", "corners", "codes",
                                             "scales"))
def _tile(pages, valid, code_refs, scale_refs, qh_ref, ckg_ref, bde_ref,
          m_ref, l_ref, acc_ref, *, keep: int, corners: tuple[int, ...],
          codes: tuple[int, int, int], scales: tuple[int, int, int]):
    """One G-page tile of `_attend_paged_kernel` for every KV head: returns
    each head's new m, l and acc."""
    hkv, nh = qh_ref.shape[1], qh_ref.shape[2]
    _, c_ppr, c_words = codes
    _, s_ppr, s_words = scales
    rows = [_page_rows(code_refs[g], pages[g] if c_ppr > 1 else None,
                       keep=keep, ppr=c_ppr, words=c_words)
            for g in range(len(pages))]
    s_offs = [(p % s_ppr) * s_words if s_ppr > 1 else None for p in pages]
    ckg = [ckg_ref[g] for g in range(len(pages))]
    m_out, l_out, acc_out = [], [], []
    for h in range(hkv):
        # Scores: the query arrives pre-transformed per head-dim block (qh[j]
        # = q_j C8[:k]^T), so a page's K rows are never rebuilt.
        qh = [qh_ref[0, h, j] for j in range(nh)]              # (n_rep, k)
        k0, v0 = h * nh, (hkv + h) * nh
        s = jnp.zeros((qh[0].shape[0], valid.shape[1]), jnp.float32)
        for g in range(len(pages)):
            s = s + _page_scores(qh, rows[g], scale_refs[g], s_offs[g],
                                 ckg[g], keep=keep, first=k0,
                                 corners=corners[k0:k0 + nh])
        s = jnp.where(valid, s, -jnp.inf)

        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe),
                          0.0)
        l_out.append(l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True))

        # P @ V the same way round: the row-side IDCT folds into P (ph = P
        # C8-placed^T), then the column-side IDCT places block j at its 8
        # output lanes.
        ys = [None] * nh
        for g in range(len(pages)):
            ph = _dot_nt(p, ckg[g])                             # (n_rep, k)
            for j, y in enumerate(_page_values(
                    ph, rows[g], scale_refs[g], s_offs[g], keep=keep,
                    first=v0, corners=corners[v0:v0 + nh])):
                ys[j] = y if ys[j] is None else jax.lax.add(ys[j], y)
        pv_out = _dot(ys[0], bde_ref[0])
        for j in range(1, nh):
            pv_out = pv_out + _dot(ys[j], bde_ref[j])           # (n_rep, hd)
        acc_out.append(acc_ref[h] * alpha + pv_out)
        m_out.append(m_new)
    return m_out, l_out, acc_out


def _attend_paged_kernel(
    pos_ref,                    # scalar prefetch: (B,) int32
    bt_ref,                     # scalar prefetch: (B, nblocks) int32 page ids
    layer_ref,                  # scalar prefetch: (1,) int32 layer index
    *refs,                      # G code rows, G scale rows, qh, q, ckg, bde, tails, out, scratch
    g_pages: int, hkv: int, n_pages: int, scale: float, keep: int,
    codes: tuple[int, int, int], scales: tuple[int, int, int],
    corners: tuple[int, ...],
):
    code_refs = refs[:g_pages]
    scale_refs = refs[g_pages:2 * g_pages]
    (qh_ref, q_ref, ckg_ref, bde_ref, tk_ref, tv_ref, o_ref,
     m_ref, l_ref, acc_ref) = refs[2 * g_pages:]
    b = pl.program_id(0)
    step = pl.program_id(1)             # one G-page tile per grid step
    tile_s = g_pages * BLOCK

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    flushed = (pos_ref[b] // BLOCK) * BLOCK
    tile0 = step * tile_s

    @pl.when(tile0 < flushed)           # skip tiles wholly past the watermark
    def _tile_step():
        pages = [jnp.clip(bt_ref[b, step * g_pages + g], 0, n_pages - 1)
                 for g in range(g_pages)]
        kv_pos = tile0 + jax.lax.broadcasted_iota(jnp.int32, (1, tile_s), 1)
        valid = kv_pos < flushed        # flushed blocks only
        m, l, acc = _tile(pages, valid, code_refs, scale_refs, qh_ref,
                          ckg_ref, bde_ref, m_ref, l_ref, acc_ref, keep=keep,
                          corners=corners, codes=codes, scales=scales)
        for h in range(hkv):
            l_ref[h] = l[h]
            acc_ref[h] = acc[h]
            m_ref[h] = m[h]

    @pl.when(step == pl.num_programs(1) - 1)
    def _finalize():
        # fused raw-tail merge: positions flushed..pos sit in the 8-token
        # tail ring; same online-softmax algebra, then normalize — the
        # kernel output is the finished attention, no XLA pass after it.
        tail_pos = flushed + jax.lax.broadcasted_iota(jnp.int32, (1, BLOCK), 1)
        tvalid = tail_pos <= pos_ref[b]
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32) * scale
            tk = tk_ref[0, h].astype(jnp.float32)              # (8, hd)
            tv = tv_ref[0, h].astype(jnp.float32)
            st = _dot_nt(q, tk)
            st = jnp.where(tvalid, st, -jnp.inf)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=-1, keepdims=True))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            pt = jnp.where(tvalid, jnp.exp(st - m_safe), 0.0)
            alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe),
                              0.0)
            l2 = l_ref[h] * alpha + jnp.sum(pt, axis=-1, keepdims=True)
            acc2 = acc_ref[h] * alpha + _dot(pt, tv)
            o_ref[0, h] = acc2 / jnp.maximum(l2, 1e-30)


def attend_paged(
    pos: jax.Array,          # (B,) int32 per-slot positions
    block_table: jax.Array,  # (B, nblocks) page ids (maybe a bucket slice)
    layer: jax.Array,        # () int32: which layer of the stacked pool
    codes: jax.Array,        # (L, rows, 1, 128) int32: `rows_codes`
    scales: jax.Array,       # (L, rows, 1, 128) f32:   `rows_scale`
    q: jax.Array,            # (B, Hkv, n_rep, hd)
    tail_k: jax.Array,       # (B, 8, Hkv, hd) raw tail ring
    tail_v: jax.Array,
    *,
    layout,                  # core.page_rows.RowLayout of the pool
    n_pages: int,
    pages_per_tile: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused decompress+attend+tail over the PAGED pool, one (batch row,
    G-page tile) per grid step and every KV head inside it.

    The pool is the segment's STACKED row storage (`core.page_rows`): per
    layer, a page's int8 corners for K and V and all KV heads are
    `rows_per_page` rows of 128 words (4 bytes a word; a corner that would
    cross a row's end starts the next row, so every keep is read), its f32
    scales a 128-word row or a slot of one.  The kernel fetches by (layer, page):
    the layer index rides the scalar-prefetch path after the positions and
    the block table, and each grid step's BlockSpec index_maps dereference
    `bt[b, i*G + g]` for the tile's G pages — one DMA for a page's codes,
    one for its scales, into VMEM and SMEM.  The pool is never sliced per
    layer, so no relayout copy of it exists outside the kernel.  Unmapped
    table entries are 0 (a valid page, masked by the flushed watermark) and
    ids outside the pool clamp to its ends, as in interpret mode; tiles
    wholly past the watermark skip compute via pl.when.  `block_table` may
    be a decode-ladder bucket slice of the full table — the grid covers
    only the slice. `pages_per_tile` shrinks to the largest divisor of the
    table width (G=1 reproduces single-page stepping).

    The corners are unpacked with shifts and masks into (k, 128) f32 rows;
    a (head, block) corner is a static lane window of them.  Scores and
    P @ V then run in the DCT domain with the same 2-D products, in the
    same order, as over the unpacked planes (see `_attend_paged_kernel`).

    Returns the NORMALIZED attention output (B, Hkv, n_rep, hd) f32 — the
    raw-tail merge runs in the kernel's finalize step.
    """
    interpret = resolve_interpret(interpret)
    assert layout.codec == "dct", layout
    k, hkv, hd = layout.keep, layout.n_kv_heads, layout.head_dim
    nh = hd // BLOCK
    cg = layout.group(page_rows.CODES)
    sg = layout.group(page_rows.ROW_PREFIX + "scale")
    b, _, n_rep, _ = q.shape
    nblocks = block_table.shape[1]
    g_pages = fit_tile(pages_per_tile, nblocks, unit=1)
    scale = 1.0 / float(np.sqrt(hd))
    ckg, bde = _paged_consts_np(k, nh, g_pages)
    # query per head-dim block, forward-DCT'd along the block and truncated
    # to the kept k frequencies: qh[b, h, j, r, v] = sum_c q[.., j*8+c] C8[v, c]
    qh = jnp.einsum("bhrjc,vc->bhjrv",
                    q.astype(jnp.float32).reshape(b, hkv, n_rep, nh, BLOCK),
                    jnp.asarray(_dct_k_np(k)), precision=_HI) * scale
    tk = jnp.swapaxes(tail_k, 1, 2)     # (B, Hkv, 8, hd)
    tv = jnp.swapaxes(tail_v, 1, 2)

    def page(bt, bi, i, g):
        # clamp like the interpreter's dynamic slice does: a compiled DMA
        # past the pool halts the chip instead
        return jnp.clip(bt[bi, i * g_pages + g], 0, n_pages - 1)

    def row_spec(grp, memory_space=None):
        ppr, rpp = grp.pages_per_row, grp.rows_per_page
        specs = []
        for g in range(g_pages):
            def idx(bi, i, pos, bt, lay, g=g):
                p = page(bt, bi, i, g)
                return (lay[0], p // ppr if ppr > 1 else p, 0, 0)
            kw = {} if memory_space is None else {"memory_space": memory_space}
            specs.append(pl.BlockSpec((None, rpp, 1, 128), idx, **kw))
        return specs

    const = lambda *shape: pl.BlockSpec(
        shape, lambda bi, i, pos, bt, lay: (0,) * len(shape))
    per_row = lambda *shape: pl.BlockSpec(
        (1,) + shape, lambda bi, i, pos, bt, lay: (bi,) + (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nblocks // g_pages),
        in_specs=row_spec(cg) + row_spec(sg, pltpu.SMEM) + [
            per_row(hkv, nh, n_rep, k),      # qh
            per_row(hkv, n_rep, hd),         # q (tail merge)
            const(g_pages, k, g_pages * BLOCK),
            const(nh, k, hd),
            per_row(hkv, BLOCK, hd),         # tail_k, head-major
            per_row(hkv, BLOCK, hd),         # tail_v
        ],
        out_specs=per_row(hkv, n_rep, hd),
        scratch_shapes=[
            pltpu.VMEM((hkv, n_rep, 1), jnp.float32),   # m
            pltpu.VMEM((hkv, n_rep, 1), jnp.float32),   # l
            pltpu.VMEM((hkv, n_rep, hd), jnp.float32),  # acc
        ],
    )
    geom = lambda grp: (grp.rows_per_page, grp.pages_per_row, grp.words)
    # the same pool arrays are passed once per tile lane: each lane's
    # BlockSpec walks its own table stride, XLA aliases the operands
    return pl.pallas_call(
        functools.partial(_attend_paged_kernel, g_pages=g_pages, hkv=hkv,
                          keep=k, n_pages=n_pages, scale=scale,
                          codes=geom(cg), scales=geom(sg),
                          corners=tuple(layout.corner_word(n)
                                        for n in range(2 * hkv * nh))),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, n_rep, hd), jnp.float32),
        interpret=interpret,
        name="attend_paged",
    )(pos.astype(jnp.int32), block_table.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      *([codes] * g_pages), *([scales] * g_pages),
      qh, q, jnp.asarray(ckg), jnp.asarray(bde), tk, tv)
