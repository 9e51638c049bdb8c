"""CompressionPlan API tests: spec roundtrip, legacy-scalar parity, the
budget solver, and a non-uniform plan working end-to-end through the
continuous-batching serve engine (acceptance criteria of the per-layer
policy redesign)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.codec.plan import BLOCK, CompressionPlan, LayerPolicy, as_plan
from repro.core import kv_cache as KV
from repro.data.synthetic import TokenStream
from repro.models import api as model_api
from repro.parallel import mesh as mesh_lib
from repro.serve import engine as E


# ---------------------------------------------------------------------------
# Spec strings
# ---------------------------------------------------------------------------

def test_spec_parse_examples():
    p = CompressionPlan.from_spec("0-3:keep=6,4-:keep=3")
    assert p.keeps(6) == (6, 6, 6, 6, 3, 3)
    assert p.segments(6) == (
        (0, 4, LayerPolicy(keep=6)), (4, 6, LayerPolicy(keep=3)))
    # single-layer entry, flags, backend, first-match override
    q = CompressionPlan.from_spec("2:keep=8+backend=reference,0-:keep=4+bits=6")
    assert q.policy(2) == LayerPolicy(keep=8, backend="reference")
    assert q.policy(0) == LayerPolicy(keep=4, bits=6)
    off = CompressionPlan.from_spec("3-:off,0-:keep=5")
    assert off.policy(1).enabled and not off.policy(3).enabled
    with pytest.raises(ValueError):
        CompressionPlan.from_spec("nope")
    with pytest.raises(ValueError):
        CompressionPlan.from_spec("0-3:keep=99")


def test_spec_roundtrip_hypothesis():
    pytest.importorskip("hypothesis")  # property tests; see requirements-dev.txt
    from hypothesis import given, settings
    from hypothesis import strategies as st

    policies = st.builds(
        LayerPolicy,
        keep=st.integers(1, 8),
        bits=st.sampled_from([4, 6, 8]),
        enabled=st.booleans(),
        backend=st.sampled_from([None, "reference", "pallas"]),
    )

    @st.composite
    def rules(draw):
        start = draw(st.integers(0, 30))
        stop = draw(st.one_of(st.none(), st.integers(start + 1, 40)))
        return (start, stop, draw(policies))

    @settings(max_examples=50, deadline=None)
    @given(st.lists(rules(), min_size=1, max_size=5))
    def roundtrip(rule_list):
        plan = CompressionPlan(rules=tuple(rule_list))
        back = CompressionPlan.from_spec(plan.to_spec())
        assert back.rules == plan.rules
        assert back.policies(16) == plan.policies(16)

    roundtrip()


def test_as_plan_spellings():
    assert as_plan(None, keep=6) == CompressionPlan.uniform(6)
    assert as_plan(5) == CompressionPlan.uniform(5)
    assert as_plan("0-:keep=3") == CompressionPlan.from_spec("0-:keep=3")
    p = as_plan("0-:keep=3", backend="reference")
    assert p.policy(0).backend == "reference"
    with pytest.raises(TypeError):
        as_plan(3.5)


def test_pyramid_is_gentle_early_aggressive_late():
    keeps = CompressionPlan.pyramid(8, keep_first=8, keep_last=3).keeps(8)
    assert keeps[0] == 8 and keeps[-1] == 3
    assert all(a >= b for a, b in zip(keeps, keeps[1:]))


# ---------------------------------------------------------------------------
# Budget solver
# ---------------------------------------------------------------------------

def test_from_budget_fits_and_is_monotone():
    cfg = model_api.get_config("yi_6b").reduced()
    max_seq, batch = 64, 2
    full = CompressionPlan.uniform(8).kv_cache_bytes(cfg, max_seq, batch=batch)
    prev_keeps = None
    for frac in (1.0, 0.8, 0.6, 0.45):
        budget = full * frac
        plan = CompressionPlan.from_budget(cfg, max_seq, budget, batch=batch)
        got = plan.kv_cache_bytes(cfg, max_seq, batch=batch)
        assert got <= budget, (frac, got, budget)
        keeps = plan.keeps(cfg.n_layers)
        if prev_keeps is not None:  # smaller budget => pointwise <= keeps
            assert all(a <= b for a, b in zip(keeps, prev_keeps)), (keeps, prev_keeps)
        prev_keeps = keeps
    # the solved plan's analytic bytes match the allocated pool exactly
    cache = KV.init_compressed_cache(cfg, batch, max_seq, plan=plan,
                                     dtype=jnp.bfloat16)
    assert cache.storage_stats()["kv_bytes"] == plan.kv_cache_bytes(
        cfg, max_seq, batch=batch)


def test_from_budget_infeasible_raises():
    cfg = model_api.get_config("yi_6b").reduced()
    with pytest.raises(ValueError):
        CompressionPlan.from_budget(cfg, 64, 1.0)


# ---------------------------------------------------------------------------
# Uniform-plan vs legacy-scalar bitwise parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm():
    api = model_api.build_reduced("yi_6b")
    params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    return api, params


def test_uniform_plan_matches_legacy_scalar_kv(lm):
    """plan=uniform(k) and kv_keep=k produce bitwise-identical prefill and
    compressed-cache decode logits."""
    api, params = lm
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, api.cfg.vocab_size, (2, 24)).astype(np.int32))
    legacy = E.ServeConfig(max_seq=64, kv_compress=True, kv_keep=6,
                           codec_backend="reference")
    planned = E.ServeConfig(max_seq=64, kv_compress=True,
                            plan=CompressionPlan.uniform(6),
                            codec_backend="reference")
    pf_a, dec_a, _, _ = E.make_steps(api, legacy)
    pf_b, dec_b, _, _ = E.make_steps(api, planned)
    la, ca = pf_a(params, toks)
    lb, cb = pf_b(params, toks)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    t = jnp.argmax(la[:, -1], -1).astype(jnp.int32)
    for s in range(5):
        la, ca = dec_a(params, t, ca, jnp.int32(24 + s))
        lb, cb = dec_b(params, t, cb, jnp.int32(24 + s))
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        t = jnp.argmax(la, -1).astype(jnp.int32)


def test_uniform_plan_matches_legacy_scalar_actcompress(lm):
    """ActCompress grads are bitwise-identical between compress_keep=k and
    plan=uniform(k) (the shim is a pure respelling)."""
    api, params = lm
    ts = TokenStream(vocab_size=api.cfg.vocab_size, seq_len=32, global_batch=4)
    batch = {k: jnp.asarray(v) for k, v in ts.batch(0).items()}

    def grads(**kw):
        return jax.grad(
            lambda p: api.loss(p, batch, remat="compressed", **kw)[0])(params)

    g_legacy = grads(compress_keep=6, codec_backend="reference")
    g_plan = grads(plan=CompressionPlan.uniform(6, backend="reference"))
    g_spec = grads(plan="0-:keep=6+backend=reference")
    for a, b, c in zip(jax.tree.leaves(g_legacy), jax.tree.leaves(g_plan),
                       jax.tree.leaves(g_spec)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_segmented_actcompress_runs_and_descends(lm):
    """A non-uniform ActCompress plan (scan split per segment) still yields
    finite, descent-aligned gradients."""
    api, params = lm
    ts = TokenStream(vocab_size=api.cfg.vocab_size, seq_len=32, global_batch=4)
    batch = {k: jnp.asarray(v) for k, v in ts.batch(0).items()}
    g_none = jax.grad(lambda p: api.loss(p, batch, remat="none")[0])(params)
    g_seg = jax.grad(lambda p: api.loss(
        p, batch, remat="compressed", plan="0-1:keep=8,2-:keep=6")[0])(params)
    num = sum(float(jnp.sum(a * b)) for a, b in
              zip(jax.tree.leaves(g_none), jax.tree.leaves(g_seg)))
    na = np.sqrt(sum(float(jnp.sum(a * a)) for a in jax.tree.leaves(g_none)))
    nb = np.sqrt(sum(float(jnp.sum(b * b)) for b in jax.tree.leaves(g_seg)))
    assert np.isfinite(num) and num / (na * nb) > 0.5


# ---------------------------------------------------------------------------
# Per-layer geometry in the KV cache
# ---------------------------------------------------------------------------

def test_cache_segments_have_per_layer_geometry():
    cfg = model_api.get_config("yi_6b").reduced()
    plan = CompressionPlan.from_spec("0-1:keep=6,2-:keep=3")
    cache = KV.init_compressed_cache(cfg, 2, 32, plan=plan)
    assert [(s.start, s.stop, s.keep) for s in cache.segments] == \
        [(0, 2, 6), (2, 4, 3)]
    assert cache.segments[0].packed_k.shape[-2:] == (6, 6)
    assert cache.segments[1].packed_k.shape[-2:] == (3, 3)
    assert cache.keeps == (6, 6, 3, 3)
    with pytest.raises(ValueError):
        cache.packed_k  # single-store view is only for uniform plans
    # uniform plans keep the legacy single-store view
    uni = KV.init_compressed_cache(cfg, 2, 32, keep=4)
    assert uni.keep == 4 and uni.packed_k.shape[0] == cfg.n_layers
    # slot reset reaches every segment
    dirty = jax.tree.map(lambda a: a + jnp.ones_like(a), cache)
    wiped = KV.cache_reset_slot(dirty, 1)
    for seg in wiped.segments:
        for leaf in jax.tree.leaves(seg):
            arr = np.asarray(leaf)
            assert (arr[:, 1] == 0).all() and (arr[:, 0] != 0).any()


# ---------------------------------------------------------------------------
# Acceptance: non-uniform plan end-to-end through the serve engine
# ---------------------------------------------------------------------------

PLENS = [5, 9, 12, 16, 3, 21, 8, 14]
MAX_NEWS = [3, 7, 5, 9, 4, 6, 8, 5]


def _requests(seed=42):
    rng = np.random.default_rng(seed)
    return [E.Request(uid=i, prompt=rng.integers(0, 200, PLENS[i]).astype(np.int32),
                      max_new=MAX_NEWS[i]) for i in range(8)]


@pytest.fixture(scope="module")
def trained_lm():
    """Briefly trained reduced model: peaked logits make greedy argmax
    robust to the (small) keep=6-level reconstruction error, as in real
    serving — random-init logits are argmax-flipping white noise."""
    from repro.optim.adamw import AdamWConfig
    from repro.train import step as train_step

    api = model_api.build_reduced("yi_6b")
    ts = TokenStream(vocab_size=api.cfg.vocab_size, seq_len=64, global_batch=8)
    tc = train_step.TrainConfig(
        microbatches=1, remat="full", param_dtype=jnp.float32,
        optimizer=AdamWConfig(lr=3e-3, warmup_steps=3, total_steps=60))
    state = train_step.init_train_state(api, tc)
    step = jax.jit(train_step.make_train_step(
        api, mesh_lib.make_mesh((1,), ("data",)), tc), donate_argnums=(0,))
    for i in range(40):
        b = {k: jnp.asarray(v) for k, v in ts.batch(i).items()}
        state, _ = step(state, b)
    return api, state["params"]


@pytest.mark.slow
def test_pyramid_plan_serves_like_uniform_with_smaller_footprint(trained_lm):
    """Acceptance: a pyramid plan through the continuous-batching engine
    reproduces the uniform-plan greedy outputs on the tested prompts while
    storage_stats reports a strictly smaller compressed KV footprint."""
    api, params = trained_lm
    cfg = api.cfg
    pyr_plan = CompressionPlan.pyramid(cfg.n_layers, keep_first=8, keep_last=6)
    assert len(set(pyr_plan.keeps(cfg.n_layers))) > 1  # genuinely non-uniform
    uni = E.ServeConfig(max_seq=64, kv_compress=True, kv_keep=8,
                        codec_backend="reference")
    pyr = E.ServeConfig(max_seq=64, kv_compress=True, plan=pyr_plan,
                        codec_backend="reference")
    out_u = E.Engine(api, params, uni, batch=4).generate(_requests())
    out_p = E.Engine(api, params, pyr, batch=4).generate(_requests())
    for u, p in zip(out_u, out_p):
        assert p.done and p.out_tokens == u.out_tokens, (p.uid,)
    su = KV.init_compressed_cache(cfg, 4, 64, keep=8).storage_stats()
    sp = KV.init_compressed_cache(cfg, 4, 64, plan=pyr_plan).storage_stats()
    assert sp["kv_bytes"] < su["kv_bytes"]
    assert sp["keeps"] == pyr_plan.keeps(cfg.n_layers)


def test_moe_segments_cross_stack_boundary():
    """A plan segment straddling the dense/moe param-stack boundary decodes
    through the compressed pool (segment x stack intersection scans)."""
    api = model_api.build_reduced("moonshot_v1_16b_a3b")
    cfg = api.cfg
    assert cfg.family == "moe" and cfg.first_k_dense == 1
    params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32))
    sc = E.ServeConfig(max_seq=32, kv_compress=True,
                       plan="0-1:keep=8,2-:keep=5", codec_backend="reference")
    pf, dec, _, _ = E.make_steps(api, sc)
    logits, cache = pf(params, toks)
    assert [(s.start, s.stop, s.keep) for s in cache.segments] == \
        [(0, 2, 8), (2, 4, 5)]
    t = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    for s in range(3):
        logits, cache = dec(params, t, cache, jnp.int32(16 + s))
        t = jnp.argmax(logits, -1).astype(jnp.int32)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_storage_accounting_single_definition():
    """Pool reports, codec stats and the analytic plan accounting all derive
    from `codec.api.tile_bytes` — pin them against each other AND against
    the literal array buffers so the definitions can't drift again."""
    from repro import codec
    from repro.codec.api import tile_bytes

    cfg = model_api.get_config("yi_6b").reduced()
    batch, max_seq = 3, 64
    plan = as_plan("0-1:keep=8,2-:keep=4")
    cache = KV.init_compressed_cache(cfg, batch, max_seq, plan=plan,
                                     dtype=jnp.bfloat16)

    for seg in cache.segments:
        literal = (seg.packed_k.size + seg.packed_v.size            # int8
                   + 4 * (seg.scale_k.size + seg.scale_v.size)      # f32
                   + 2 * (seg.tail_k.size + seg.tail_v.size))       # bf16
        assert seg.nbytes() == literal

    # cache report == plan analytic == engine pool report (eval_shape bytes)
    assert cache.storage_stats()["kv_bytes"] == \
        plan.kv_cache_bytes(cfg, max_seq, batch=batch)
    api = model_api.build_reduced("yi_6b")
    params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = E.Engine(api, params,
                   E.ServeConfig(max_seq=max_seq, kv_compress=True, plan=plan,
                                 codec_backend="reference"), batch=batch)
    assert eng.kv_pool_stats()["kv_pool_bytes"] == \
        plan.kv_cache_bytes(cfg, max_seq, batch=batch)

    # per-token view matches the codec container's bytes-per-element
    for keep in (3, 4, 6, 8):
        c = codec.compress(jnp.ones((16, 16), jnp.float32), keep=keep)
        assert c.nbytes_per_element() == tile_bytes(keep) / 64
        stats = codec.storage_stats(c)
        assert stats["compressed_bits"] == \
            (16 // 8) * (16 // 8) * tile_bytes(keep) * 8
        uni = KV.init_compressed_cache(cfg, 1, 64, keep=keep)
        hd, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
        assert uni.nbytes_per_token_per_layer() == \
            KV.block_group_bytes(keep, hkv, hd) / 8 == \
            2 * hkv * hd * c.nbytes_per_element()

    # the paged pool charges the same per-block definition
    paged = KV.init_paged_cache(cfg, batch, max_seq, n_pages=10, plan=plan)
    hd, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
    assert paged.page_bytes() == plan.page_bytes(cfg) == sum(
        KV.block_group_bytes(k, hkv, hd) for k in plan.keeps(cfg.n_layers))
