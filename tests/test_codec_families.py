"""Codec-family contract: plane trees, pack/unpack exactness, byte
accounting, plan threading, and the pinned proof that the refactor left the
default dct path bitwise identical.

The pinned literals in `BASELINE` were captured from the pre-refactor tree
(commit 29d5032) with the exact serve configuration `_baseline_serve` uses:
greedy tokens of 8 requests through a 4-slot paged pool, plus the pool's
analytic byte stats.  The refactored cache MUST reproduce them token for
token and byte for byte — the dct family is the old layout behind a new
seam, not a new codec.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.codec import api as codec_api
from repro.codec import families as families_lib
from repro.codec import plan as plan_lib
from repro.core import encode as encode_lib
from repro.core import kv_cache as kvc
from repro.core import page_rows
from repro.models import api as model_api
from repro.serve import engine as E

BLOCK = 8


def _quantized_blocks(rng, shape=(3, 2, 4), keep=5, zero_frac=0.5):
    """Random quantized tiles in the (..., nh, k, k) + scale form the block
    codec emits: int8 coefficients with a realistic zero fraction, and a few
    all-zero tiles (zero scale) mixed in."""
    q = rng.integers(-127, 128, shape + (keep, keep)).astype(np.int8)
    q = np.where(rng.random(q.shape) < zero_frac, 0, q)
    scale = rng.random(shape).astype(np.float32) * 3.0
    dead = rng.random(shape) < 0.15
    q = np.where(dead[..., None, None], 0, q)
    scale = np.where(dead, 0.0, scale)
    return jnp.asarray(q), jnp.asarray(scale)


# ---------------------------------------------------------------------------
# Registry + plane tree
# ---------------------------------------------------------------------------

def test_registry_declares_three_families():
    assert families_lib.available_families() == ["asc", "bitplane", "dct"]
    assert families_lib.get_family(None).name == "dct"  # None => default
    with pytest.raises(KeyError, match="unknown codec family"):
        families_lib.get_family("zstd")


def test_every_family_declares_packed_carrier():
    for name in families_lib.available_families():
        fam = families_lib.get_family(name)
        specs = {s.name: s for s in fam.plane_specs(5, 32)}
        assert "packed" in specs
        assert specs["packed"].block_shape == (4, 5, 5)  # (hd/8, k, k)
        assert specs["packed"].dtype == jnp.int8


def test_plane_block_ndims_consistent():
    # one global name -> rank table (what sharding dispatches on)
    nd = families_lib.plane_block_ndims()
    assert nd["packed"] == 3 and nd["scale"] == 1
    assert nd["bpmask"] == 2 and nd["blen"] == 1 and nd["sexp"] == 1


def test_register_rejects_conflicting_plane_rank():
    class Bad(families_lib.CodecFamily):
        name = "bad"

        def plane_specs(self, keep, head_dim):
            return (families_lib.PlaneSpec("packed", jnp.int8, (1, keep, keep)),
                    families_lib.PlaneSpec("scale", jnp.float32, (1, 2)))

    with pytest.raises(ValueError, match="already registered with rank"):
        families_lib.register_family(Bad())
    assert "bad" not in families_lib.available_families()


# ---------------------------------------------------------------------------
# Pack/unpack exactness + byte accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["dct", "bitplane", "asc"])
@pytest.mark.parametrize("keep", [2, 5, 8])
def test_pack_unpack_int8_exact(name, keep):
    """The int8 coefficient blocks survive every family's plane layout
    bitwise (scales may round where the family declares an adaptive
    header — the coefficients never do)."""
    fam = families_lib.get_family(name)
    q, scale = _quantized_blocks(np.random.default_rng(0), keep=keep)
    planes = fam.pack(q, scale, keep)
    assert set(p.name for p in fam.plane_specs(keep, 32)) == set(planes)
    q2, scale2 = fam.unpack(planes, keep)
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(q))
    if name in ("dct", "bitplane"):
        np.testing.assert_array_equal(np.asarray(scale2), np.asarray(scale))


def test_asc_scale_error_bounded():
    fam = families_lib.get_family("asc")
    q, scale = _quantized_blocks(np.random.default_rng(1), keep=4)
    _, scale2 = fam.unpack(fam.pack(q, scale, 4), 4)
    s, s2 = np.asarray(scale), np.asarray(scale2)
    # zero scales reconstruct exactly (reserved code); the rest within an
    # eighth of an octave: rel err < 2**(1/16) - 1
    np.testing.assert_array_equal(s2[s == 0], 0.0)
    live = s > 0
    rel = np.abs(s2[live] - s[live]) / s[live]
    assert rel.max() <= 2 ** (1 / 16) - 1 + 1e-6


@pytest.mark.parametrize("name", ["dct", "bitplane", "asc"])
@pytest.mark.parametrize("keep", [2, 5, 8])
def test_analytic_upper_bounds_measured(name, keep):
    fam = families_lib.get_family(name)
    for zero_frac in (0.0, 0.5, 1.0):
        q, _ = _quantized_blocks(np.random.default_rng(2), keep=keep,
                                 zero_frac=zero_frac)
        bits = np.asarray(fam.measured_tile_bits(q))
        assert bits.shape == q.shape[:-2]
        assert (bits <= 8 * fam.analytic_tile_bytes(keep)).all(), \
            (name, keep, zero_frac)


def test_bitplane_blen_matches_numpy_rle_reference():
    """The bitplane family's stored per-tile length is EXACTLY the repo's
    one RLE accounting (`core.encode.rle_codec_bits`), including the
    all-zero and fully-dense edge cases — reused, not reimplemented."""
    fam = families_lib.get_family("bitplane")
    rng = np.random.default_rng(3)
    keep = 5
    tiles = [np.zeros((keep, keep), np.int8),                  # all zero
             rng.integers(1, 127, (keep, keep)).astype(np.int8)]  # dense
    for zf in (0.2, 0.6, 0.9, 0.97):
        t = rng.integers(-127, 128, (keep, keep)).astype(np.int8)
        tiles.append(np.where(rng.random(t.shape) < zf, 0, t))
    q = jnp.asarray(np.stack(tiles))
    planes = fam.pack(q, jnp.ones(len(tiles), jnp.float32), keep)
    blen = np.asarray(planes["blen"])
    for i, t in enumerate(tiles):
        want = encode_lib.rle_codec_bits(t.reshape(-1), fam.VALUE_BITS,
                                         fam.RUN_BITS)
        assert int(blen[i]) == want, (i, int(blen[i]), want)


def test_rle_tiles_matches_numpy_on_long_runs():
    # saturated-run edge: runs far beyond maxrun=31, and a trailing run
    rng = np.random.default_rng(4)
    for n in (31, 32, 63, 200):
        x = np.zeros(n, np.int8)
        x[0] = 1  # long trailing zero run
        rows = [x, np.zeros(n, np.int8),
                rng.integers(-5, 6, n).astype(np.int8)]
        got = np.asarray(encode_lib.rle_codec_bits_tiles(
            jnp.asarray(np.stack(rows)), 8, 5))
        for r, g in zip(rows, got):
            assert int(g) == encode_lib.rle_codec_bits(r, 8, 5)


def test_family_compress_roundtrip_through_backend():
    """compress/decompress entry points: planes in, activations out, equal
    to the raw block-codec roundtrip for every family (bitwise for
    dct/bitplane; asc within its scale-step bound)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 24, 16)).astype(np.float32))
    q, scale = codec_api.compress_blocks(x, 4, backend="reference")
    want = codec_api.decompress_blocks(q, scale, backend="reference")
    for name in families_lib.available_families():
        fam = families_lib.get_family(name)
        planes = fam.compress(x, 4, backend="reference")
        got = fam.decompress(planes, 4, backend="reference")
        assert got.shape == want.shape
        if name == "asc":
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2 ** (1 / 16) - 1 + 1e-5,
                                       atol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Plan threading: spec grammar, validation, budget solver over curves
# ---------------------------------------------------------------------------

def test_plan_spec_codec_roundtrip():
    spec = "0-1:keep=6,2-:keep=4+codec=bitplane"
    plan = plan_lib.CompressionPlan.from_spec(spec)
    pols = plan.policies(4)
    assert [p.codec for p in pols] == ["dct", "dct", "bitplane", "bitplane"]
    assert plan.to_spec() == spec  # codec= token survives the round trip


def test_plan_spec_errors_name_token_and_position():
    # "0-1:keep=6,2-:kep=4" — the bad token starts at character 14
    with pytest.raises(ValueError) as ei:
        plan_lib.CompressionPlan.from_spec("0-1:keep=6,2-:kep=4")
    msg = str(ei.value)
    assert "'kep=4'" in msg and "position 14" in msg

    # "0-:keep=4+codec=zstd" — unknown family rejected at parse, char 10
    with pytest.raises(ValueError) as ei:
        plan_lib.CompressionPlan.from_spec("0-:keep=4+codec=zstd")
    msg = str(ei.value)
    assert "'codec=zstd'" in msg and "position 10" in msg
    assert "asc" in msg  # names the families that DO exist


def test_layer_policy_rejects_unknown_codec():
    with pytest.raises(ValueError, match="unknown codec family 'nope'"):
        plan_lib.LayerPolicy(keep=4, codec="nope")


def test_with_codec_and_as_plan_override_everywhere():
    plan = plan_lib.as_plan("0-1:keep=6,2-:keep=4", codec="asc")
    assert all(p.codec == "asc" for p in plan.policies(4))
    # int / None spellings too
    assert all(p.codec == "bitplane"
               for p in plan_lib.as_plan(5, codec="bitplane").policies(3))


class _Cfg:
    n_layers = 4
    n_kv_heads = 2
    resolved_head_dim = 32


def test_layer_bytes_per_token_per_family():
    # hd=32 -> nh=4 tiles/head; 2 (K+V) * 2 heads * 4 tiles / 8 tokens
    # = 2 tiles/token; dct keep=4: tile 20 B -> 40 B/token
    f = plan_lib.CompressionPlan._layer_bytes_per_token
    assert f(_Cfg, plan_lib.LayerPolicy(keep=4)) == 40.0
    assert f(_Cfg, plan_lib.LayerPolicy(keep=4, codec="asc")) == 34.0
    bp = families_lib.get_family("bitplane").analytic_tile_bytes(4)
    assert f(_Cfg, plan_lib.LayerPolicy(keep=4, codec="bitplane")) == 2 * bp


def test_from_budget_curves_selects_mixed_plan():
    curves = [
        {"codec": "dct", "keep": 8, "ppl_delta": 0.01},
        {"codec": "dct", "keep": 4, "ppl_delta": 0.30},
        {"codec": "bitplane", "keep": 4, "ppl_delta": 0.25},
        {"codec": "asc", "keep": 3, "ppl_delta": 0.90},
        # dominated: costs more than dct@8 at worse quality -> off frontier
        {"codec": "bitplane", "keep": 8, "ppl_delta": 0.50},
    ]
    loose = plan_lib.CompressionPlan.from_budget(
        _Cfg, 64, 1e9, curves=curves)
    assert all(p.codec == "dct" and p.kv_keep == 8
               for p in loose.policies(4))

    # a budget that fits dct@8 on some layers but not all: the solver walks
    # the deepest layers down the frontier first
    per_layer8 = plan_lib.CompressionPlan._layer_bytes_per_token(
        _Cfg, plan_lib.LayerPolicy(keep=8))
    tail = _Cfg.n_layers * 2 * BLOCK * _Cfg.n_kv_heads * \
        _Cfg.resolved_head_dim * 2
    budget = (2.5 * per_layer8 + 1.5 * 34.0) * 64 + tail  # ~2-3 layers at dct@8
    mixed = plan_lib.CompressionPlan.from_budget(
        _Cfg, 64, budget, curves=curves)
    pols = mixed.policies(4)
    assert {(p.codec, p.kv_keep) for p in pols} > {("dct", 8)}  # truly mixed
    assert mixed.kv_cache_bytes(_Cfg, 64) <= budget
    # monotone: a smaller budget only ever moves layers DOWN the frontier
    frontier_rank = {("dct", 8): 0, ("bitplane", 4): 1, ("dct", 4): 2,
                     ("asc", 3): 3}
    tight = plan_lib.CompressionPlan.from_budget(
        _Cfg, 64, budget * 0.7, curves=curves)
    for a, b in zip(pols, tight.policies(4)):
        assert frontier_rank[(b.codec, b.kv_keep)] >= \
            frontier_rank[(a.codec, a.kv_keep)]

    with pytest.raises(ValueError, match="infeasible"):
        plan_lib.CompressionPlan.from_budget(_Cfg, 64, 1.0, curves=curves)


# ---------------------------------------------------------------------------
# Cache containers: segment planes follow the declaration
# ---------------------------------------------------------------------------

def test_segment_planes_follow_family_declaration():
    plan = plan_lib.as_plan("0-1:keep=6,2-:keep=4+codec=bitplane")
    cache = kvc.init_paged_cache(_Cfg, batch=2, max_seq=64, n_pages=16,
                                 plan=plan, dtype=jnp.float32)
    segs = cache.segments
    assert [s.codec for s in segs] == ["dct", "bitplane"]
    assert segs[0].page_keys == ("packed_k", "packed_v", "scale_k", "scale_v")
    assert segs[1].page_keys == ("blen_k", "blen_v", "bpmask_k", "bpmask_v",
                                 "packed_k", "packed_v", "scale_k", "scale_v")
    # paged plane geometry: (Lseg, P, Hkv) + block_shape in plane form,
    # stored as 128-word rows: bitplane's int8 corners and mask bytes share
    # the code rows (2 x 2 heads x 4 blocks x (4 + 1) words = 80 a page),
    # its int32 lengths and f32 scales take rows of their own (16 words a
    # page, 8 pages a row)
    shapes = page_rows.segment_layout(segs[1]).plane_shapes()
    assert shapes["bpmask_k"] == ((2, 4, 2), jnp.uint8)
    assert shapes["blen_k"] == ((2, 4), jnp.int32)
    assert segs[1].row_keys == ("rows_blen", "rows_codes", "rows_scale")
    assert segs[1].planes["rows_codes"].shape == (2, 16, 1, 128)
    assert segs[1].planes["rows_blen"].shape == (2, 2, 1, 128)
    assert segs[1].planes["rows_scale"].shape == (2, 2, 1, 128)
    # analytic page bytes charge each segment's own family
    bp = families_lib.get_family("bitplane").analytic_tile_bytes(4)
    want = (2 * 2 * 2 * 4 * codec_api.tile_bytes(6)) + (2 * 2 * 2 * 4 * bp)
    assert cache.page_bytes() == want


def test_measured_cache_bytes_bounded_by_analytic():
    plan = plan_lib.as_plan("0-1:keep=6,2-:keep=4+codec=bitplane")
    cache = kvc.init_paged_cache(_Cfg, batch=2, max_seq=64, n_pages=16,
                                 plan=plan, dtype=jnp.float32)
    # empty pool: only the raw tails are resident
    tails = sum(int(np.prod(s.planes[n].shape)) * 4
                for s in cache.segments for n in kvc.TAIL_NAMES)
    assert kvc.measured_cache_bytes(cache) == tails


def test_tier_manager_mirrors_family_planes():
    """Host tier allocates each segment's OWN plane set (not the legacy
    dct 4-tuple) and the stage_out -> read_back round trip is bitwise for
    non-dct planes too."""
    from repro.serve import tiering
    plan = plan_lib.as_plan("0-1:keep=6,2-:keep=4+codec=asc")
    mk = lambda: kvc.init_paged_cache(_Cfg, batch=2, max_seq=64, n_pages=6,
                                      plan=plan, dtype=jnp.float32)
    tier = tiering.TierManager(jax.eval_shape(mk), host_pages=4)
    assert tier._page_keys[0] == ("packed_k", "packed_v",
                                  "scale_k", "scale_v")
    assert tier._page_keys[1] == ("packed_k", "packed_v",
                                  "sexp_k", "sexp_v")
    rng = np.random.default_rng(7)
    cache = jax.tree.map(
        lambda l: jnp.asarray(rng.standard_normal(l.shape) * 8)
        .astype(l.dtype), mk())
    ids = jnp.asarray(np.array([0, 1], np.int32))
    upd = kvc.paged_gather_slot(cache, jnp.int32(0), ids)
    hids = tier.alloc(2)
    tier.stage_out(hids, jax.tree.map(np.asarray, upd))
    back = tier.read_back(list(enumerate(hids)), nbkt=2)
    for seg_b, seg_u, keys in zip(back, upd, tier._page_keys):
        for key in keys:
            np.testing.assert_array_equal(
                np.asarray(seg_b[key]), np.asarray(seg_u[key]), err_msg=key)


# ---------------------------------------------------------------------------
# Pinned dct bitwise parity with the pre-refactor tree
# ---------------------------------------------------------------------------

PLENS = [5, 9, 12, 16, 3, 21, 8, 14]
MAX_NEWS = [3, 7, 5, 9, 4, 6, 8, 5]
PYRAMID = "0-1:keep=8,2-:keep=4"

# captured at commit 29d5032 (pre-refactor) — single-device and 4x1 mesh
# produce identical streams there, so one literal pins both paths here
BASELINE = {
    "uniform": {
        "tokens": [[206, 84, 84],
                   [118, 118, 118, 177, 177, 96, 118],
                   [167, 102, 107, 121, 34],
                   [49, 100, 60, 255, 159, 78, 17, 56, 74],
                   [20, 206, 34, 64],
                   [49, 80, 4, 49, 232, 49],
                   [69, 39, 49, 118, 118, 118, 118, 69],
                   [3, 101, 39, 232, 51]],
        "kv_pool_bytes": 47232, "page_bytes": 640, "pool_pages": 48,
    },
    "pyramid": {
        "tokens": [[206, 84, 84],
                   [118, 22, 235, 59, 79, 59, 79],
                   [167, 34, 194, 228, 34],
                   [49, 49, 253, 253, 253, 253, 178, 91, 253],
                   [20, 206, 34, 64],
                   [49, 49, 249, 193, 253, 49],
                   [69, 231, 77, 69, 77, 79, 79, 34],
                   [3, 84, 84, 185, 219]],
        "kv_pool_bytes": 84096, "page_bytes": 1408, "pool_pages": 48,
    },
}


@pytest.fixture(scope="module")
def lm():
    api = model_api.build_reduced("yi_6b")
    params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    return api, params


def _requests(n=8, seed=42):
    rng = np.random.default_rng(seed)
    return [E.Request(uid=i,
                      prompt=rng.integers(0, 200, PLENS[i]).astype(np.int32),
                      max_new=MAX_NEWS[i]) for i in range(n)]


def _baseline_serve(api, params, plan, mesh=None):
    sc = E.ServeConfig(max_seq=64, kv_compress=True, plan=plan,
                       codec_backend="reference", mesh=mesh, pool_pages=48)
    eng = E.Engine(api, params, sc, batch=4)
    done = eng.generate(_requests())
    assert all(r.done for r in done)
    return [list(map(int, r.out_tokens)) for r in done], eng.kv_pool_stats()


@pytest.mark.parametrize("plan_name,plan",
                         [("uniform", 4), ("pyramid", PYRAMID)])
def test_dct_bitwise_parity_pinned(lm, plan_name, plan):
    """The refactored dct path reproduces the pre-refactor greedy stream and
    pool accounting EXACTLY — the family seam is pure layout."""
    api, params = lm
    toks, stats = _baseline_serve(api, params, plan)
    want = BASELINE[plan_name]
    assert toks == want["tokens"]
    assert int(stats["kv_pool_bytes"]) == want["kv_pool_bytes"]
    assert int(stats["page_bytes"]) == want["page_bytes"]
    assert int(stats["pool_pages"]) == want["pool_pages"]


@pytest.mark.slow
@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
@pytest.mark.parametrize("plan_name,plan",
                         [("uniform", 4), ("pyramid", PYRAMID)])
def test_dct_bitwise_parity_pinned_mesh(lm, plan_name, plan):
    """Same pinned literals on a 4x1 serve mesh: the generic plane-name
    sharding rules place the refactored pool exactly like the old
    hard-coded packed/scale rules did."""
    from repro.parallel import mesh as mesh_lib

    api, params = lm
    toks, stats = _baseline_serve(api, params, plan,
                                  mesh=mesh_lib.make_serve_mesh("4x1"))
    want = BASELINE[plan_name]
    assert toks == want["tokens"]
    assert int(stats["kv_pool_bytes"]) == want["kv_pool_bytes"]


# ---------------------------------------------------------------------------
# Mixed-codec plans serve end to end
# ---------------------------------------------------------------------------

MIXED = "0-1:keep=6,2-:keep=4+codec=bitplane"


@pytest.mark.parametrize("plan", [MIXED, "0-:keep=4+codec=asc"],
                         ids=["mixed_dct_bitplane", "uniform_asc"])
def test_non_dct_plans_serve_paged_e2e(lm, plan):
    """Non-default families thread plan -> pool -> engine: requests complete
    through the paged pool, the pool reports both analytic and measured
    bytes, and measured never exceeds the analytic allocation."""
    api, params = lm
    sc = E.ServeConfig(max_seq=64, kv_compress=True, plan=plan,
                       codec_backend="reference", pool_pages=48)
    eng = E.Engine(api, params, sc, batch=4)
    done = eng.generate(_requests())
    assert all(r.done for r in done)
    assert [len(r.out_tokens) for r in done] == MAX_NEWS
    stats = eng.kv_pool_stats()
    assert stats["measured_kv_bytes"] > 0
    # the pool served 8 short requests through 48 pages: the data-dependent
    # footprint must sit well inside the analytic allocation
    assert stats["measured_kv_bytes"] <= stats["kv_pool_bytes"]


def test_mixed_plan_greedy_matches_uniform_prefix_layers(lm):
    """Sanity on semantics, not bits: a mixed plan with bitplane (lossless
    repack of the same quantized blocks) on layers 2+ must produce exactly
    the tokens of the all-dct plan with the same keeps — bitplane changes
    storage, never values."""
    api, params = lm
    base, _ = _baseline_serve(api, params, "0-1:keep=6,2-:keep=4")
    got, _ = _baseline_serve(api, params, MIXED)
    assert got == base
