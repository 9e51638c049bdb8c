"""Tiered page pool: host offload of cold compressed pages + copy-on-write
prefix sharing.

The device pool (PR 5-7) is the paper's on-chip feature-map buffer; the
host tier is its off-chip DRAM, affordable because pages move compressed
(int8 DCT blocks + scales). These tests pin the two correctness contracts:

  * TIERING IS PLACEMENT ONLY — greedy tokens with forced eviction (device
    pool barely one request's horizon) are bitwise the untiered pool's, on
    uniform + pyramid plans, single-device and 4x1 mesh, with the page
    ledger (`check_page_invariants`) balancing after every admission flush
    and retirement (`paranoid_pool_checks`).
  * SHARING IS STORAGE ONLY — identical prompt prefixes map the same
    physical pages, admission reserves just the unshared suffix, and a
    forced hash collision costs a demotion (fresh pages), never aliased
    output: the device-side bitwise verification, not the hash, is the
    safety boundary.

Fast tests cover the host-side allocator pieces (TierManager round trip,
PrefixIndex, config resolution) and a small-model engine parity; the
yi_6b engine parities and the mesh leg are `slow` (tier1-mesh job).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kv_cache as KV
from repro.models import api as model_api
from repro.parallel import mesh as mesh_lib
from repro.serve import engine as E
from repro.serve import tiering

PLENS = [5, 9, 12, 16, 3, 21, 8, 14]
MAX_NEWS = [3, 7, 5, 9, 4, 6, 8, 5]
PYRAMID = "0-1:keep=8,2-:keep=4"


@pytest.fixture(scope="module")
def lm_small():
    api = model_api.build_reduced("qwen2_0_5b")
    params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    return api, params


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


def _shared_prefix_requests(n=8, seed=7, pre_tokens=16, suf_tokens=4,
                            max_new=12):
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, 200, pre_tokens).astype(np.int32)
    return [E.Request(uid=i, prompt=np.concatenate(
        [pre, rng.integers(0, 200, suf_tokens).astype(np.int32)]),
        max_new=max_new) for i in range(n)]


def _parity(base, got):
    for a, b in zip(base, got):
        assert a.out_tokens == b.out_tokens, \
            (a.uid, a.out_tokens, b.out_tokens)


# ---------------------------------------------------------------------------
# Prefix hash: hypothesis-free mirror of the property tests
# ---------------------------------------------------------------------------

def test_prefix_block_keys_properties():
    """Chained content keys: full blocks only, pure function of the tokens,
    padding/extension invariant, divergent from the first differing block.
    (The hypothesis version lives in test_prefix_hash_property.py.)"""
    rng = np.random.default_rng(0)
    for plen in (0, 3, 8, 11, 16, 29, 64):
        arr = rng.integers(0, 2**31 - 1, plen).astype(np.int32)
        keys = tiering.prefix_block_keys(arr)
        assert len(keys) == plen // 8
        assert keys == tiering.prefix_block_keys(arr)  # deterministic
        # batch-padding / extension invariance: appending anything never
        # rewrites a completed block's key
        padded = np.concatenate([arr, rng.integers(0, 99, 13).astype(np.int32)])
        assert tiering.prefix_block_keys(padded)[:len(keys)] == keys
        if plen >= 8:
            for flip in (0, plen // 2, 8 * (plen // 8) - 1):
                mut = arr.copy()
                mut[flip] ^= 1
                km = tiering.prefix_block_keys(mut)
                blk = flip // 8
                assert km[:blk] == keys[:blk]
                assert all(a != b for a, b in zip(km[blk:], keys[blk:]))


def test_prefix_index_bimap_and_leading_run():
    idx = tiering.PrefixIndex()
    ka, kb, kc = b"a", b"b", b"c"
    idx.register(ka, 3)
    idx.register(kb, 5)
    idx.register(ka, 9)  # first writer wins
    assert idx.lookup_run([ka, kb, kc]) == [3, 5]
    assert idx.lookup_run([kc, ka]) == []      # run must be LEADING
    idx.drop_page(3)                           # freed/spilled page leaves
    assert idx.lookup_run([ka, kb]) == []
    assert len(idx) == 1
    idx.register(ka, 7)                        # key is re-registerable
    assert idx.lookup_run([ka, kb]) == [7, 5]


# ---------------------------------------------------------------------------
# TierManager: host store round trip is bitwise
# ---------------------------------------------------------------------------

def test_tier_manager_roundtrip_bitwise(lm_small):
    """gather -> stage_out -> read_back -> paged_write_slot returns page
    content (packed int8, f32 scales, bf16 tails) bit-for-bit."""
    api, _ = lm_small
    cfg = api.cfg
    mk = lambda: KV.init_paged_cache(cfg, 2, 32, 6)
    rng = np.random.default_rng(3)
    cache = jax.tree.map(
        lambda l: jnp.asarray(rng.standard_normal(l.shape) * 8).astype(l.dtype),
        mk())
    ids = jnp.asarray(np.array([0, 1, 2], np.int32))
    upd = KV.paged_gather_slot(cache, jnp.int32(0), ids)

    tier = tiering.TierManager(jax.eval_shape(mk), host_pages=5)
    assert tier.free_pages == 5 and tier.in_use == 0
    hids = tier.alloc(3)
    assert tier.in_use == 3
    with pytest.raises(RuntimeError, match="host page pool exhausted"):
        tier.alloc(3)
    tier.stage_out(hids, jax.tree.map(np.asarray, upd))

    back = tier.read_back(list(enumerate(hids)), nbkt=3)
    back = [dict(seg, **{k: np.asarray(u[k]) for k in tiering.TAIL_KEYS})
            for seg, u in zip(back, upd)]
    row = np.zeros(32 // 8, np.int32)
    row[:3] = [3, 4, 5]
    restored = KV.paged_write_slot(mk(), back, jnp.int32(1),
                                   jnp.asarray(row[:3]), jnp.asarray(row))
    upd2 = KV.paged_gather_slot(restored, jnp.int32(1),
                                jnp.asarray(np.array([3, 4, 5], np.int32)))
    for seg_a, seg_b in zip(upd, upd2):
        for key in tiering.PAGE_KEYS:
            np.testing.assert_array_equal(np.asarray(seg_a[key]),
                                          np.asarray(seg_b[key]), err_msg=key)
    tier.release(hids)
    assert tier.free_pages == 5


# ---------------------------------------------------------------------------
# Config plumbing
# ---------------------------------------------------------------------------

def test_resolved_host_pages_and_validation(lm_small):
    api, params = lm_small
    cfg = api.cfg
    page_b = E.ServeConfig(kv_compress=True, kv_keep=8) \
        .resolved_plan().page_bytes(cfg)
    sc = E.ServeConfig(kv_compress=True, kv_keep=8, pool_pages=4,
                       host_pool_mb=(10 * page_b) / 1e6)
    assert sc.tiered and sc.resolved_host_pages(cfg) == 10
    assert E.ServeConfig(kv_compress=True, kv_keep=8, pool_pages=4,
                         host_pool_pages=7).resolved_host_pages(cfg) == 7
    with pytest.raises(ValueError, match="holds no page"):
        E.ServeConfig(kv_compress=True, kv_keep=8, pool_pages=4,
                      host_pool_mb=1e-9).resolved_host_pages(cfg)
    # tiering/sharing ride the paged allocator; a dense pool has no pages
    for kw in ({"host_pool_pages": 8}, {"prefix_sharing": True}):
        with pytest.raises(ValueError, match="paged KV pool"):
            E.Engine(api, params,
                     E.ServeConfig(max_seq=32, kv_compress=True, kv_keep=8,
                                   **kw), batch=2)


# ---------------------------------------------------------------------------
# Engine parity, small model (fast) — forced offload + sharing together
# ---------------------------------------------------------------------------

def test_tiered_and_shared_parity_small(lm_small):
    """qwen2-reduced: device pool of 4 pages + host tier + prefix sharing
    serves the mixed workload bitwise-identically to a big untiered pool,
    with the ledger checked after every admission/retirement."""
    _tiered_and_shared_parity(lm_small)


def test_donated_pool_tiered_and_shared_parity_small(lm_small, monkeypatch):
    """The same with the decode step donating (consuming) its pool, as the
    engine builds it on a TPU: park, resume, prefix verification and reset
    never touch a pool already handed to a step, and tokens are unchanged."""
    real_jit, donated = jax.jit, []

    def jit(fn, *args, **kw):
        donated.append(kw.get("donate_argnums", ()))
        return real_jit(fn, *args, **kw)

    def on_tpu():
        monkeypatch.setattr(E.jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(E.jax, "jit", jit)

    _tiered_and_shared_parity(lm_small, before_engine=on_tpu)
    assert (2,) in donated


def _tiered_and_shared_parity(lm_small, before_engine=lambda: None):
    api, params = lm_small
    kw = dict(max_seq=64, kv_compress=True, kv_keep=8,
              codec_backend="reference")
    base = E.Engine(api, params, E.ServeConfig(**kw, pool_pages=32),
                    batch=4).generate(_requests())
    before_engine()
    eng = E.Engine(api, params,
                   E.ServeConfig(**kw, pool_pages=4, host_pool_pages=32,
                                 prefix_sharing=True), batch=4)
    eng.paranoid_pool_checks = True
    got = eng.generate(_requests())
    _parity(base, got)
    assert eng.stats["slots_parked"] == eng.stats["slots_resumed"]
    assert eng.stats["pages_spilled"] == eng.stats["pages_restored"]
    assert eng.stats["slots_parked"] > 0   # the tiny pool forced offload
    st = eng.kv_pool_stats()               # runs check_page_invariants()
    assert st["pages_host_in_use"] == 0    # everything streamed back
    assert sorted(eng._free_pages) == list(range(4))


# ---------------------------------------------------------------------------
# Engine parity, yi_6b (slow): uniform + pyramid, offload / sharing legs
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("plan", [8, PYRAMID], ids=["uniform", "pyramid"])
def test_host_offload_bitwise_matches_untiered(lm, plan):
    """Acceptance: eviction forced by a 4-page device pool (vs 32 untiered)
    changes NOTHING about the tokens — spill/restore is placement only."""
    api, params = lm
    kw = dict(max_seq=64, kv_compress=True, plan=plan,
              codec_backend="reference")
    base = E.Engine(api, params, E.ServeConfig(**kw, pool_pages=32),
                    batch=4).generate(_requests())
    eng = E.Engine(api, params,
                   E.ServeConfig(**kw, pool_pages=4, host_pool_pages=32,
                                 aot_warmup=True), batch=4)
    eng.paranoid_pool_checks = True
    snap = eng.trace_counts.snapshot()
    got = eng.generate(_requests())
    _parity(base, got)
    assert eng.trace_counts.delta(snap) == {}  # fault path rode the warmup
    assert eng.stats["slots_parked"] > 0
    assert eng.stats["pages_spilled"] > 0
    assert eng.stats["pages_spilled"] == eng.stats["pages_restored"]
    eng.kv_pool_stats()
    assert sorted(eng._free_pages) == list(range(4))  # full drain


@pytest.mark.slow
@pytest.mark.parametrize("plan", [8, PYRAMID], ids=["uniform", "pyramid"])
def test_prefix_sharing_bitwise_and_page_counts(lm, plan):
    """Acceptance: sharing on vs off is bitwise; N slots sharing a 2-block
    prefix peak at exactly 1x prefix + Nx suffix-horizon physical pages."""
    api, params = lm
    n = 4
    kw = dict(max_seq=64, kv_compress=True, plan=plan,
              codec_backend="reference", pool_pages=2 + n * 1,
              aot_warmup=True)
    base = E.Engine(api, params, E.ServeConfig(**kw), batch=n) \
        .generate(_shared_prefix_requests(n))
    eng = E.Engine(api, params, E.ServeConfig(**kw, prefix_sharing=True),
                   batch=n)
    eng.paranoid_pool_checks = True
    snap = eng.trace_counts.snapshot()
    got = eng.generate(_shared_prefix_requests(n))
    _parity(base, got)
    assert eng.trace_counts.delta(snap) == {}
    st = eng.kv_pool_stats()
    # (16+4+12-1)//8 = 3 pages/request: 2 shared + 1 own suffix. Stored
    # once: peak = 2 + n, and every slot ran concurrently at a budget the
    # unshared engine cannot even fit two full reservations into.
    assert st["peak_pages_in_use"] == 2 + n
    assert st["prefix_shared_blocks"] == 2 * (n - 1)
    assert st["prefix_demotions"] == 0
    assert eng.stats["peak_live_slots"] == n
    assert sorted(eng._free_pages) == list(range(2 + n))


@pytest.mark.slow
def test_hash_collision_demotes_instead_of_aliasing(lm):
    """Force total hash collisions (constant key_fn): every admission sees
    bogus share candidates, the device-side bitwise verification rejects
    them, and outputs stay exactly the unshared engine's — the hash is an
    optimization, the verification is the correctness boundary."""
    api, params = lm
    kw = dict(max_seq=64, kv_compress=True, kv_keep=8,
              codec_backend="reference", pool_pages=32)
    base = E.Engine(api, params, E.ServeConfig(**kw), batch=4) \
        .generate(_requests())
    eng = E.Engine(api, params, E.ServeConfig(**kw, prefix_sharing=True),
                   batch=4)
    eng.paranoid_pool_checks = True
    eng._prefix.key_fn = \
        lambda prompt: [b"collide"] * (len(prompt) // KV.BLOCK)
    got = eng.generate(_requests())
    _parity(base, got)
    assert eng.stats["prefix_demotions"] > 0   # collisions were caught
    eng.kv_pool_stats()
    assert sorted(eng._free_pages) == list(range(32))


@pytest.mark.slow
@pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs 4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)")
@pytest.mark.parametrize("plan", [8, PYRAMID], ids=["uniform", "pyramid"])
def test_tiered_and_shared_parity_on_4x1_mesh(lm, plan):
    """Acceptance: the 4x1 mesh engine with host offload + prefix sharing
    (host pages OUTSIDE the mesh, restores re-placed with the pool's
    sharding) is bitwise the single-device untiered engine."""
    api, params = lm
    kw = dict(max_seq=64, kv_compress=True, plan=plan,
              codec_backend="reference")
    base = E.Engine(api, params, E.ServeConfig(**kw, pool_pages=32),
                    batch=4).generate(_requests())
    eng = E.Engine(api, params,
                   E.ServeConfig(**kw, pool_pages=4, host_pool_pages=32,
                                 prefix_sharing=True, aot_warmup=True,
                                 mesh=mesh_lib.make_serve_mesh("4x1")),
                   batch=4)
    eng.paranoid_pool_checks = True
    snap = eng.trace_counts.snapshot()
    got = eng.generate(_requests())
    _parity(base, got)
    assert eng.trace_counts.delta(snap) == {}
    assert eng.stats["slots_parked"] > 0
    eng.kv_pool_stats()
    assert sorted(eng._free_pages) == list(range(4))
