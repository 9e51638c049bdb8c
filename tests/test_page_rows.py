"""Row storage of the paged KV pool (`core.page_rows`).

  * every codec family's page planes survive plane form -> words -> rows ->
    words -> plane form bitwise, at keep 3, 4, 6 and 8 and head_dim 64 and
    128 (shared rows, whole rows, padded last rows and rows whose ends are
    skipped among them);
  * the row geometry: 128-word rows, pages sharing a row when they fit
    twice, else whole rows per page, and no dct corner across two rows;
  * the `kernels/page_write` kernel (interpreted here) writes one layer's
    pages exactly as the drop-mode XLA scatter does, pages that share a
    row flushing together and dropped ids included.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.codec import families as families_lib
from repro.core import page_rows

HKV = 2


def _random(shape, dtype, rng):
    """Random bits of `dtype`: every f32 pattern (NaN payloads and -0.0
    included), so only a bitwise copy round-trips."""
    dt = np.dtype(jnp.dtype(dtype))
    raw = rng.integers(0, 256, size=tuple(shape) + (dt.itemsize,),
                       dtype=np.uint8)
    return jnp.asarray(raw.view(dt).reshape(shape))


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint8) if a.dtype.itemsize == 1 else a.view(np.uint32)


def _planes(layout, lead, rng):
    return {name: _random(lead + shape, dt, rng)
            for name, (shape, dt) in layout.plane_shapes().items()}


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("keep", [3, 4, 6, 8])
@pytest.mark.parametrize("codec", families_lib.available_families())
def test_pages_round_trip_through_rows(codec, keep, head_dim):
    rng = np.random.default_rng(keep * head_dim)
    layers, n_pages = 2, 11          # 11 pages: a shared last row is partial
    layout = page_rows.row_layout(codec, keep, HKV, head_dim)
    specs = families_lib.get_family(codec).plane_specs(keep, head_dim)
    assert layout.plane_names == tuple(sorted(
        s.name + sfx for s in specs for sfx in ("_k", "_v")))
    planes = _planes(layout, (layers, n_pages), rng)

    words = page_rows.pack(layout, planes)
    for g in layout.groups:
        assert words[g.key].shape == (layers, n_pages, g.words)
        assert jnp.dtype(words[g.key].dtype).itemsize == 4
    back = page_rows.unpack(layout, words)
    for name, a in planes.items():
        assert back[name].dtype == a.dtype and back[name].shape == a.shape
        np.testing.assert_array_equal(_bits(back[name]), _bits(a), name)

    # through the stored rows, pages in scrambled order
    perm = jnp.asarray(rng.permutation(n_pages), jnp.int32)
    rows = page_rows.scatter_planes(
        layout, page_rows.zeros(layout, layers, n_pages), perm, planes,
        n_pages)
    for g in layout.groups:
        assert rows[g.key].shape == (layers, g.n_rows(n_pages), 1, 128)
    got = page_rows.gather_planes(layout, rows, perm, n_pages)
    for name, a in planes.items():
        np.testing.assert_array_equal(_bits(got[name]), _bits(a), name)
    # one layer at a time reads the same pages
    one = page_rows.gather_planes(layout, rows, perm[:3], n_pages, layer=1)
    for name, a in planes.items():
        np.testing.assert_array_equal(_bits(one[name]), _bits(a[1, :3]), name)


@pytest.mark.parametrize("head_dim,keep,codes_rows,scale_pages_per_row", [
    (64, 4, 1, 4),     # qwen2-0.5B: 2 x 2 heads x 8 blocks x 4 words
    (128, 4, 2, 2),    # hd 128, 2 heads: 256 code words, 64 scales
    (64, 3, 1, 4),     # keep 3: 96 code words, padded to one row
    (64, 6, 4, 4),     # keep 6: 10 corners of 12 words a row, 384 -> 512
    (64, 8, 4, 4),     # keep 8: 2 words a corner row, 512 code words
])
def test_dct_row_geometry(head_dim, keep, codes_rows, scale_pages_per_row):
    layout = page_rows.row_layout("dct", keep, HKV, head_dim)
    codes = layout.group(page_rows.CODES)
    scale = layout.group("rows_scale")
    assert codes.rows_per_page == codes_rows and codes.pages_per_row == 1
    assert scale.pages_per_row == scale_pages_per_row
    # yi-6B's pool: 4 code rows and one scale row a page, no padding
    yi = page_rows.row_layout("dct", 4, 4, 128)
    assert yi.group(page_rows.CODES).rows_per_page == 4
    assert yi.group("rows_scale").rows_per_page == 1
    stored = sum(np.prod(shape) * 4 for shape, _ in yi.shapes(1, 1792).values())
    assert stored == 1792 * (2048 + 512)


@pytest.mark.parametrize("head_dim,hkv", [(64, 2), (128, 4)])
@pytest.mark.parametrize("keep", range(1, 9))
def test_dct_corners_never_straddle_rows(keep, head_dim, hkv):
    """Every (K|V, head, block) corner's k * ceil(k/4) words lie in one row
    of its page, in order and apart from every other corner; the skipped
    words at a row's end are the only padding inside a page."""
    layout = page_rows.row_layout("dct", keep, hkv, head_dim)
    codes = layout.group(page_rows.CODES)
    bw = keep * -(-keep // 4)
    n = 2 * hkv * (head_dim // 8)
    starts = [layout.corner_word(i) for i in range(n)]
    assert starts == sorted(starts) and starts[0] == 0
    for a, b in zip(starts, starts[1:]):
        assert b - a == bw or (b % 128 == 0 and b - a < 2 * bw), (a, b)
    for w in starts:
        assert w % 128 + bw <= 128, w
    assert codes.words == starts[-1] + bw
    per_row = 128 // bw
    assert codes.rows_per_page == (1 if codes.words <= 128
                                   else -(-n // per_row))


@pytest.mark.parametrize("codec,keep,head_dim", [
    ("dct", 4, 64),        # codes one row a page, scales 4 pages a row
    ("dct", 4, 128),       # codes two rows a page, scales 2 pages a row
    ("bitplane", 4, 64),   # int32 lengths in shared rows, padded codes
    ("asc", 3, 128),       # codes and exponent bytes in the same rows
])
def test_page_write_kernel_matches_scatter(codec, keep, head_dim):
    rng = np.random.default_rng(keep + head_dim)
    layers, n_pages, layer = 3, 13, 1
    layout = page_rows.row_layout(codec, keep, HKV, head_dim)
    # pages 4, 5, 6 and 7 flush together (they share rows where rows are
    # shared); 13 and -1 are out of range and dropped
    pages = jnp.asarray([5, n_pages, 6, 4, 12, -1, 7], jnp.int32)
    for g in layout.groups:
        arr = _random(layout.shapes(layers, n_pages)[g.key][0], g.dtype, rng)
        words = _random((pages.shape[0], g.words), g.dtype, rng)
        want = page_rows.scatter_pages(g, arr, pages, words, n_pages, layer)
        got = page_rows.write_layer_pages(g, arr, jnp.int32(layer), pages,
                                          words, n_pages, interpret=True)
        np.testing.assert_array_equal(_bits(got), _bits(want), g.key)
        # the written pages read back; nothing else moved
        read = page_rows.gather_pages(g, got, pages[jnp.asarray([0, 2, 3])],
                                      n_pages, layer)
        np.testing.assert_array_equal(_bits(read),
                                      _bits(words[jnp.asarray([0, 2, 3])]))
        np.testing.assert_array_equal(_bits(got[0]), _bits(arr[0]))
        np.testing.assert_array_equal(_bits(got[2]), _bits(arr[2]))
