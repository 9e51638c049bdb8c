"""Paged compressed KV pool: block-granular allocation, parity and reuse.

The pool is the paper's dynamically-allocated feature-map buffer taken
literally: a shared page pool (one page = one 8-token DCT block group across
all layers) addressed through per-slot block tables, with the serve engine's
host-side free list as the allocator. These tests pin:

  * bitwise greedy parity with the dense pool (uniform + pyramid plans,
    reference backend) while pages are not exhausted,
  * admission blocking on free-page count + freed-page reuse,
  * O(prompt) admission — nothing max_seq-sized in the prefill/splice path,
  * the paged attend primitives (reference gather and fused kernel) against
    the dense layout.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kv_cache as KV
from repro.core import page_rows
from repro.models import api as model_api
from repro.serve import engine as E

PLENS = [5, 9, 12, 16, 3, 21, 8, 14]
MAX_NEWS = [3, 7, 5, 9, 4, 6, 8, 5]
PYRAMID = "0-1:keep=8,2-:keep=4"


@pytest.fixture(scope="module")
def lm():
    api = model_api.build_reduced("yi_6b")
    params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    return api, params


def _requests(n=8, seed=42):
    rng = np.random.default_rng(seed)
    return [E.Request(uid=i, prompt=rng.integers(0, 200, PLENS[i]).astype(np.int32),
                      max_new=MAX_NEWS[i]) for i in range(n)]


# ---------------------------------------------------------------------------
# Primitive parity: paged update/attend == dense update/attend
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_paged_update_and_attend_match_dense(lm):
    """Feed the same tokens through a dense layer cache and a paged layer
    cache (host-assigned pages in a scrambled order): flushed blocks land in
    the mapped pages bit-for-bit and attention output is bitwise equal."""
    api, _ = lm
    cfg = api.cfg
    b, max_seq, keep, n_pages = 3, 64, 6, 13
    hd, hkv, h = cfg.resolved_head_dim, cfg.n_kv_heads, cfg.n_heads
    nh = hd // 8
    rng = np.random.default_rng(1)
    depth = 29
    ks = jnp.asarray(rng.standard_normal((b, depth, hkv, hd)).astype(np.float32))
    vs = jnp.asarray(rng.standard_normal((b, depth, hkv, hd)).astype(np.float32))

    dense = {
        "packed_k": jnp.zeros((b, max_seq // 8, hkv, nh, keep, keep), jnp.int8),
        "scale_k": jnp.zeros((b, max_seq // 8, hkv, nh), jnp.float32),
        "packed_v": jnp.zeros((b, max_seq // 8, hkv, nh, keep, keep), jnp.int8),
        "scale_v": jnp.zeros((b, max_seq // 8, hkv, nh), jnp.float32),
        "tail_k": jnp.zeros((b, 8, hkv, hd), jnp.float32),
        "tail_v": jnp.zeros((b, 8, hkv, hd), jnp.float32),
    }
    # the page pool: one layer of row storage (core.page_rows) + tails
    layout = page_rows.row_layout("dct", keep, hkv, hd)
    pool = page_rows.zeros(layout, 1, n_pages)
    assert pool["rows_codes"].shape == (1, n_pages, 1, 128)
    assert pool["rows_scale"].shape == (1, 1, 1, 128)  # 16 pages a row
    tails = {"tail_k": jnp.zeros((b, 8, hkv, hd), jnp.float32),
             "tail_v": jnp.zeros((b, 8, hkv, hd), jnp.float32)}
    # scrambled host allocation: page for (row, block) in arbitrary order
    perm = rng.permutation(n_pages)
    page_of = {(i, j): int(perm[(i * 4 + j) % n_pages])
               for i in range(b) for j in range(4)}
    table = np.zeros((b, max_seq // 8), np.int32)

    for t in range(depth):
        posv = jnp.full((b,), t, jnp.int32)
        kn, vn = ks[:, t:t + 1], vs[:, t:t + 1]
        dense = KV.update_layer(dense, kn, vn, posv, keep)
        if t % 8 == 7:
            fp = np.array([page_of[(i, t // 8)] for i in range(b)], np.int32)
            for i in range(b):
                table[i, t // 8] = fp[i]
        else:
            fp = np.full((b,), n_pages, np.int32)
        pool, tails = KV.update_layer_paged(pool, jnp.int32(0), tails, kn, vn,
                                            posv, jnp.asarray(fp), keep,
                                            n_pages=n_pages)

    # every flushed dense block is bitwise present in its mapped page
    pages = page_rows.gather_planes(layout, pool, jnp.arange(n_pages),
                                    n_pages, layer=0)
    assert pages["packed_k"].shape == (n_pages, hkv, nh, keep, keep)
    assert pages["scale_k"].shape == (n_pages, hkv, nh)
    for i in range(b):
        for j in range(depth // 8):
            for name in ("packed_k", "scale_k", "packed_v", "scale_v"):
                np.testing.assert_array_equal(
                    np.asarray(dense[name][i, j]),
                    np.asarray(pages[name][table[i, j]]),
                    err_msg=f"{name} {i},{j}")
    np.testing.assert_array_equal(np.asarray(dense["tail_k"]),
                                  np.asarray(tails["tail_k"]))

    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)).astype(np.float32))
    posq = jnp.full((b,), depth - 1, jnp.int32)
    paged = dict(pool, **tails)
    out_dense = KV.attend_compressed(q, dense, posq, keep, kv_block=16)
    out_paged = KV.attend_compressed(q, paged, posq, keep, kv_block=16,
                                     block_table=jnp.asarray(table),
                                     layer=0, n_pages=n_pages)
    np.testing.assert_array_equal(np.asarray(out_dense), np.asarray(out_paged))

    # fused kernel path (interpret): block table on the scalar-prefetch side
    from repro.kernels.fused_attend import ops as fa_ops
    out_kern = fa_ops.attend_with_tail(q, paged, posq,
                                       block_table=jnp.asarray(table),
                                       keep=keep, layer=0, n_pages=n_pages)
    np.testing.assert_allclose(np.asarray(out_kern), np.asarray(out_dense),
                               atol=1e-4)


@pytest.mark.parametrize("keep,hd,hkv", [(6, 64, 2), (3, 128, 4)],
                         ids=["keep6-hd64", "keep3-hd128-hkv4"])
def test_attend_paged_reads_corners_past_skipped_row_ends(keep, hd, hkv):
    """Layouts whose corners do not tile a 128-word row (12 and 3 words)
    start a corner that would cross a row's end on the next row: the fused
    kernel (interpreted) still reads every page as the reference gather
    does."""
    from repro.kernels.fused_attend import ops as fa_ops

    b, n_rep, n_pages, layer, n_layers = 2, 2, 7, 1, 2
    nh = hd // 8
    layout = page_rows.row_layout("dct", keep, hkv, hd)
    assert layout.group("rows_codes").words > 2 * hkv * nh * keep * \
        -(-keep // 4)                        # some row ends are skipped
    rng = np.random.default_rng(keep + hd)
    lead = (n_layers, n_pages, hkv, nh)
    planes = {}
    for sfx in ("_k", "_v"):
        planes["packed" + sfx] = jnp.asarray(
            rng.integers(-8, 8, lead + (keep, keep), np.int8))
        planes["scale" + sfx] = jnp.asarray(
            rng.uniform(0.5, 2, lead).astype(np.float32))
    cache = page_rows.scatter_planes(
        layout, page_rows.zeros(layout, n_layers, n_pages),
        jnp.asarray(rng.permutation(n_pages), jnp.int32), planes, n_pages)
    cache["tail_k"] = jnp.asarray(rng.standard_normal((b, 8, hkv, hd)).astype(np.float32))
    cache["tail_v"] = jnp.asarray(rng.standard_normal((b, 8, hkv, hd)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((b, 1, hkv * n_rep, hd)).astype(np.float32))
    pos = jnp.asarray([29, 17], jnp.int32)
    table = jnp.asarray(rng.integers(0, n_pages, (b, 4)), jnp.int32)

    paged = dict(layer=layer, n_pages=n_pages)
    ref = KV.attend_compressed(q, cache, pos, keep, kv_block=16,
                               block_table=table, **paged)
    out = fa_ops.attend_with_tail(q, cache, pos, block_table=table,
                                  pages_per_tile=2, keep=keep, **paged)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# ---------------------------------------------------------------------------
# Engine: greedy parity, exhaustion, reuse
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("plan", [8, PYRAMID], ids=["uniform", "pyramid"])
def test_paged_engine_bitwise_matches_dense(lm, plan):
    """Acceptance criterion: greedy tokens over the paged pool are bitwise
    the dense pool's when pages are not exhausted (uniform + pyramid)."""
    api, params = lm
    kw = dict(max_seq=64, kv_compress=True, plan=plan,
              codec_backend="reference")
    dense = E.Engine(api, params, E.ServeConfig(**kw), batch=4)
    base = dense.generate(_requests())
    paged = E.Engine(api, params, E.ServeConfig(**kw, pool_pages=32), batch=4)
    got = paged.generate(_requests())
    assert paged.paged and paged.stats["admit_blocked_on_pages"] == 0
    for a, b in zip(base, got):
        assert a.out_tokens == b.out_tokens, (a.uid, a.out_tokens, b.out_tokens)
    # the whole pool is free again after the workload drains
    assert sorted(paged._free_pages) == list(range(32))


@pytest.mark.slow
def test_paged_engine_keep6_hd64_matches_dense_through_kernel():
    """At keep 6 and head_dim 64 a page's corners (12 words each) skip the
    end of every code row.  Greedy tokens through the fused paged kernel
    (interpreted) are the dense pool's on the same kernel backend."""
    import dataclasses

    from repro.configs.base import get_config

    cfg = dataclasses.replace(get_config("qwen2_0_5b").reduced(), head_dim=64)
    api = model_api.build("qwen2_0_5b", cfg)
    params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    kw = dict(max_seq=32, kv_compress=True, kv_keep=6, codec_backend="pallas")
    reqs = lambda: _requests(4)
    dense = E.Engine(api, params, E.ServeConfig(**kw), batch=2).generate(reqs())
    paged = E.Engine(api, params, E.ServeConfig(**kw, pool_pages=12), batch=2)
    layout = page_rows.row_layout("dct", 6, cfg.n_kv_heads, 64)
    assert layout.group("rows_codes").rows_per_page == 4   # 384 words -> 4 rows
    got = paged.generate(reqs())
    assert paged.paged
    assert [r.out_tokens for r in got] == [r.out_tokens for r in dense]


@pytest.mark.slow
def test_pool_exhaustion_blocks_admission_and_reuses_pages(lm):
    """With a pool far smaller than slots x max_seq, admission must block on
    the free-page count (not free slots), resume on retirement with
    RE-ISSUED pages, and still produce the dense engine's tokens."""
    api, params = lm
    kw = dict(max_seq=64, kv_compress=True, kv_keep=8,
              codec_backend="reference")
    base = E.Engine(api, params, E.ServeConfig(**kw), batch=4).generate(_requests())
    eng = E.Engine(api, params, E.ServeConfig(**kw, pool_pages=4), batch=4)

    issued = []
    inner = eng._admit
    def admit_spy(r, cache, slot):
        issued.append(tuple(eng._slot_pages[slot]))
        return inner(r, cache, slot)
    eng._admit = admit_spy

    got = eng.generate(_requests())
    for a, b in zip(base, got):
        assert a.out_tokens == b.out_tokens, (a.uid,)
    assert eng.stats["admit_blocked_on_pages"] > 0   # pages gated admission
    assert eng.stats["peak_pages_in_use"] <= 4
    # pages from retired requests were re-issued to later ones
    flat = [p for pages in issued for p in pages]
    assert len(flat) > len(set(flat)), issued
    assert sorted(eng._free_pages) == list(range(4))  # all returned at drain


def test_unreserved_flush_never_enters_block_table(lm):
    """A row reaching pos%8==7 without a reserved page (a speculative step
    past its horizon, or a retired row) gets the out-of-range id: the
    decode step must leave its table entry alone, since the compiled
    attend kernel DMAs pages through the table. A row with a page maps it."""
    api, params = lm
    b, max_seq, n_pages = 3, 64, 5
    cache = KV.init_paged_cache(api.cfg, b, max_seq, n_pages, keep=8,
                                dtype=jnp.float32)
    pos = jnp.asarray([7, 15, 3], jnp.int32)   # rows 0, 1 fill their tail
    fp = jnp.asarray([n_pages, 2, n_pages], jnp.int32)
    _, out = E.decode_step_compressed(params, jnp.zeros((b,), jnp.int32),
                                      cache, pos, api.cfg,
                                      codec_backend="reference",
                                      flush_page=fp)
    table = np.asarray(out.block_table)
    assert table.min() >= 0 and table.max() < n_pages, table
    assert table[1, 1] == 2 and table[0, 0] == 0
    assert np.count_nonzero(table) == 1


def test_request_larger_than_pool_raises(lm):
    api, params = lm
    eng = E.Engine(api, params,
                   E.ServeConfig(max_seq=64, kv_compress=True, kv_keep=8,
                                 codec_backend="reference", pool_pages=2),
                   batch=2)
    big = [E.Request(uid=0, prompt=np.zeros(30, np.int32), max_new=30)]
    with pytest.raises(ValueError, match="pages"):
        eng.generate(big)


def test_failed_admission_releases_reserved_pages(lm):
    """A prompt whose bucket overruns max_seq raises AFTER pages were
    reserved (the page gate clamps to max_seq, the bucket check doesn't):
    the reservation must roll back so the pool can't leak and later
    generate() calls still have the full pool."""
    api, params = lm
    eng = E.Engine(api, params,
                   E.ServeConfig(max_seq=64, kv_compress=True, kv_keep=8,
                                 codec_backend="reference", pool_pages=16),
                   batch=2)
    too_long = [E.Request(uid=0, prompt=np.zeros(70, np.int32), max_new=2)]
    with pytest.raises(ValueError, match="bucket"):
        eng.generate(too_long)
    assert sorted(eng._free_pages) == list(range(16))  # nothing leaked
    ok = eng.generate(_requests(n=3))
    assert all(r.done for r in ok)


def test_paged_requires_compressed_continuous(lm):
    api, params = lm
    with pytest.raises(ValueError, match="paged"):
        E.Engine(api, params, E.ServeConfig(max_seq=64, pool_pages=8), batch=2)
    with pytest.raises(ValueError, match="continuous"):
        E.Engine(api, params,
                 E.ServeConfig(max_seq=64, kv_compress=True, kv_keep=8,
                               pool_pages=8),
                 batch=2, scheduler="static")


def test_page_budget_solves_page_count(lm):
    """page_budget_mb -> pages via the plan's per-layer page accounting."""
    api, params = lm
    plan = E.ServeConfig(kv_compress=True, kv_keep=8).resolved_plan()
    page_b = plan.page_bytes(api.cfg)
    sc = E.ServeConfig(max_seq=64, kv_compress=True, kv_keep=8,
                       codec_backend="reference",
                       page_budget_mb=10 * page_b / 1e6)
    assert sc.resolved_pool_pages(api.cfg) == 10
    eng = E.Engine(api, params, sc, batch=2)
    assert eng._n_pages == 10
    with pytest.raises(ValueError, match="no page"):
        E.ServeConfig(kv_compress=True, kv_keep=8,
                      page_budget_mb=page_b / 1e6 / 2).resolved_pool_pages(api.cfg)


# ---------------------------------------------------------------------------
# Admission cost: nothing max_seq-sized in the paged prefill/splice path
# ---------------------------------------------------------------------------

def test_paged_admission_never_materializes_max_seq(lm):
    """The dense path zero-fills a max_seq-deep store per admission; the
    paged path must scale with the prompt bucket only.  Checked on compiled
    shapes: every output of the paged prefill (and every operand of its
    HLO) is bucket-sized, while the dense prefill's store is max_seq-sized."""
    api, params = lm
    # large pool depth, chosen so max_seq/8 = 344 collides with no model dim
    max_seq = 2752
    bucket, plen = 16, 12
    tokens = jnp.zeros((1, bucket), jnp.int32)
    lengths = jnp.asarray([plen], jnp.int32)

    sc_dense = E.ServeConfig(max_seq=max_seq, kv_compress=True, kv_keep=8,
                             codec_backend="reference")
    pre_d, _, _, _ = E.make_steps(api, sc_dense)
    _, dense_cache = jax.eval_shape(pre_d, params, tokens, lengths)
    assert dense_cache.segments[0].packed_k.shape[2] == max_seq // 8

    sc_paged = E.ServeConfig(max_seq=max_seq, kv_compress=True, kv_keep=8,
                             codec_backend="reference", pool_pages=8)
    pre_p, _, _, _ = E.make_steps(api, sc_paged)
    _, upd = jax.eval_shape(pre_p, params, tokens, lengths)
    for seg in upd:
        for name, leaf in seg.items():
            assert max_seq // 8 not in leaf.shape, (name, leaf.shape)
            assert leaf.shape[2] in (bucket // 8, 8), (name, leaf.shape)

    # compiled-HLO check: no operand anywhere in the paged prefill carries
    # the max_seq block depth (StableHLO renders shapes 'tensor<1x344x...>',
    # so match the x-delimited dim). Positive control first: the DENSE
    # prefill's lowering must contain it — else the pattern is vacuous.
    dim = f"x{max_seq // 8}x"
    txt_dense = jax.jit(pre_d).lower(params, tokens, lengths).as_text()
    assert dim in txt_dense, "positive control failed: pattern never matches"
    txt = jax.jit(pre_p).lower(params, tokens, lengths).as_text()
    assert dim not in txt


# ---------------------------------------------------------------------------
# Pool container + accounting
# ---------------------------------------------------------------------------

def test_paged_cache_geometry_and_page_bytes(lm):
    api, _ = lm
    cfg = api.cfg
    cache = KV.init_paged_cache(cfg, batch=3, max_seq=64, n_pages=11,
                                plan=PYRAMID)
    assert cache.n_pages == 11
    assert cache.max_seq == 64
    assert cache.block_table.shape == (3, 8)
    assert [s.keep for s in cache.segments] == [8, 4]
    hd, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
    want = sum((s.stop - s.start) * KV.block_group_bytes(s.keep, hkv, hd)
               for s in cache.segments)
    assert cache.page_bytes() == want
    # the plan-level accounting (ServeConfig.page_budget_mb's solver) agrees
    from repro.codec import plan as plan_lib
    assert plan_lib.as_plan(PYRAMID).page_bytes(cfg) == want
