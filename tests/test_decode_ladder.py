"""Decode-bucket ladder + multi-page tiled paged attend (PR 7).

Pins the PR's acceptance criteria:
  * tile-width invariance — greedy decode tokens are identical across
    pages-per-tile G in {1, 2, 4, 8} (one-page G=1 is the old kernel's
    schedule) and across the fused-pallas / reference backends, dense and
    paged pools, uniform and pyramid plans;
  * the decode ladder is output-exact — bucketed engines (decode_buckets
    auto) produce bitwise the single-full-capacity-bucket engine's tokens
    (the sliced table entries can only name blocks the flushed-watermark
    mask discards anyway), single-device and 4x1 mesh;
  * zero jit traces under traffic once the ladder is warm: AOT warmup
    compiles exactly len(decode_ladder.buckets) decode steps and a
    multi-bucket workload compiles nothing more;
  * DecodeLadder bucket selection and validation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kv_cache as KV
from repro.models import api as model_api
from repro.serve import engine as E
from repro.serve import pipeline as pl

PLENS = [5, 11, 17, 8]
MAX_NEWS = [6, 5, 4, 7]
PYRAMID = "0-1:keep=8,2-:keep=4"


@pytest.fixture(scope="module")
def lm():
    api = model_api.build_reduced("yi_6b")
    params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    return api, params


def _requests(n=4, seed=7):
    rng = np.random.default_rng(seed)
    return [E.Request(uid=i,
                      prompt=rng.integers(0, 200, PLENS[i]).astype(np.int32),
                      max_new=MAX_NEWS[i]) for i in range(n)]


# ---------------------------------------------------------------------------
# Ladder unit (no model)
# ---------------------------------------------------------------------------

def test_decode_ladder_build_and_bucket_for():
    lad = pl.DecodeLadder.build(64)
    assert lad.buckets == (8, 16, 32, 64)
    assert lad.bucket_for(0) == 8
    assert lad.bucket_for(16) == 16
    assert lad.bucket_for(17) == 32
    assert lad.bucket_for(64) == 64
    with pytest.raises(ValueError, match="ladder"):
        lad.bucket_for(65)
    # off = one full-capacity bucket (the pre-ladder decode step)
    assert pl.DecodeLadder.build(64, False).buckets == (64,)
    assert pl.DecodeLadder.build(64, "off").buckets == (64,)
    # an explicit ladder is completed to max_seq: every legal watermark
    # must have a covering bucket
    assert pl.DecodeLadder.build(64, (16,)).buckets == (16, 64)
    assert pl.DecodeLadder.build(64, (64, 16)).buckets == (16, 64)
    with pytest.raises(ValueError, match="multiple"):
        pl.DecodeLadder.build(64, (12,))
    with pytest.raises(ValueError, match="max_seq"):
        pl.DecodeLadder.build(64, (128,))
    with pytest.raises(ValueError, match="empty"):
        pl.DecodeLadder.build(64, ())


# ---------------------------------------------------------------------------
# Kernel-level tile-width invariance (fast: one attend, no engine)
# ---------------------------------------------------------------------------

def test_attend_paged_tile_width_invariance():
    """One paged attend over a scrambled 13-page pool: the fused kernel's
    output is G-invariant (same flash merge, different tile schedule) and
    matches the reference gather; trailing unmapped table entries sliced
    off by table_view change nothing."""
    from repro.kernels.fused_attend import ops as fa_ops

    from repro.core import page_rows

    b, hkv, n_rep, hd, keep, n_pages = 2, 2, 2, 16, 4, 13
    nh, depth, layer = hd // 8, 29, 1
    rng = np.random.default_rng(3)
    planes = {
        "packed_k": jnp.asarray(rng.integers(-8, 8, (3, n_pages, hkv, nh, keep, keep), np.int8)),
        "scale_k": jnp.asarray(rng.uniform(0.5, 2, (3, n_pages, hkv, nh)).astype(np.float32)),
        "packed_v": jnp.asarray(rng.integers(-8, 8, (3, n_pages, hkv, nh, keep, keep), np.int8)),
        "scale_v": jnp.asarray(rng.uniform(0.5, 2, (3, n_pages, hkv, nh)).astype(np.float32)),
    }
    # a 3-layer pool in row storage; the attends read layer 1 of it.  At
    # this width 4 pages share a code row (32 words a page)
    layout = page_rows.row_layout("dct", keep, hkv, hd)
    assert layout.group("rows_codes").pages_per_row == 4
    cache = page_rows.scatter_planes(layout, page_rows.zeros(layout, 3, n_pages),
                                     jnp.arange(n_pages), planes, n_pages)
    cache["tail_k"] = jnp.asarray(rng.standard_normal((b, 8, hkv, hd)).astype(np.float32))
    cache["tail_v"] = jnp.asarray(rng.standard_normal((b, 8, hkv, hd)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((b, 1, hkv * n_rep, hd)).astype(np.float32))
    pos = jnp.asarray([depth - 1, 14], jnp.int32)  # per-row watermarks
    table = np.zeros((b, 8), np.int32)  # 64-token capacity, partly occupied
    perm = rng.permutation(n_pages)
    for i in range(b):
        for j in range(int(pos[i]) // 8):
            table[i, j] = int(perm[(i * 4 + j) % n_pages])
    table = jnp.asarray(table)

    paged = dict(layer=layer, n_pages=n_pages)
    ref = KV.attend_compressed(q, cache, pos, keep, kv_block=16,
                               block_table=table, **paged)
    outs = [fa_ops.attend_with_tail(q, cache, pos, block_table=table,
                                    pages_per_tile=g, keep=keep, **paged)
            for g in (1, 2, 4, 8)]
    for g, out in zip((1, 2, 4, 8), outs):
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, err_msg=f"G={g}")
        # G changes the flash-merge tile schedule only: bit-level drift
        # between widths stays at float32 rounding noise
        np.testing.assert_allclose(np.asarray(out), np.asarray(outs[0]),
                                   atol=1e-5, err_msg=f"G={g} vs G=1")
    # the decode-ladder slice is exact: drop trailing entries past every
    # row's watermark (max pos 28 -> 3 flushed pages + tail)
    sliced = KV.table_view(table, 4)
    out_sl = fa_ops.attend_with_tail(q, cache, pos, block_table=sliced,
                                     pages_per_tile=2, keep=keep, **paged)
    np.testing.assert_array_equal(np.asarray(out_sl), np.asarray(outs[1]))


# ---------------------------------------------------------------------------
# Engine: ladder on == ladder off, bitwise (the exactness contract)
# ---------------------------------------------------------------------------

def test_ladder_on_off_parity_single_device(lm):
    api, params = lm
    kw = dict(max_seq=32, kv_compress=True, kv_keep=8,
              codec_backend="reference", pool_pages=16)
    on = E.Engine(api, params, E.ServeConfig(**kw), batch=2)
    off = E.Engine(api, params, E.ServeConfig(**kw, decode_buckets=False),
                   batch=2)
    assert on.decode_ladder.buckets == (8, 16, 32)
    assert off.decode_ladder.buckets == (32,)
    a = on.generate(_requests())
    b = off.generate(_requests())
    assert [r.out_tokens for r in a] == [r.out_tokens for r in b]
    # the ladder actually engaged: mean dispatched bucket < full capacity
    assert 0 < on.stats["decode_bucket_tokens"] < 32 * on.stats["steps"]
    assert off.stats["decode_bucket_tokens"] == 32 * off.stats["steps"]


@pytest.mark.slow
@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")
def test_ladder_parity_on_mesh(lm):
    """4x1 mesh: the bucketed decode jits share the full-capacity step's
    shardings, so ladder on == ladder off == single-device, bitwise."""
    from repro.parallel import mesh as mesh_lib

    api, params = lm
    kw = dict(max_seq=32, kv_compress=True, kv_keep=8,
              codec_backend="reference", pool_pages=32)
    base = E.Engine(api, params, E.ServeConfig(**kw, decode_buckets=False),
                    batch=4).generate(_requests())
    eng = E.Engine(api, params,
                   E.ServeConfig(**kw, mesh=mesh_lib.make_serve_mesh("4x1")),
                   batch=4)
    got = eng.generate(_requests())
    assert [r.out_tokens for r in got] == [r.out_tokens for r in base]
    assert eng.stats["decode_bucket_tokens"] < 32 * eng.stats["steps"]


# ---------------------------------------------------------------------------
# Engine: greedy tokens are G-invariant through the fused kernel
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("plan", [8, PYRAMID], ids=["uniform", "pyramid"])
def test_engine_tile_width_invariance_pallas(lm, plan):
    """Full serve traffic through the fused paged kernel (interpret on CPU)
    at every tile width: greedy tokens must be identical across G — G=1 is
    the old one-page schedule — and match the dense-pool engine on the same
    kernel backend."""
    api, params = lm
    kw = dict(max_seq=32, kv_compress=True, plan=plan, codec_backend="pallas")
    dense = E.Engine(api, params, E.ServeConfig(**kw), batch=2) \
        .generate(_requests())
    toks = {}
    for g in (1, 2, 4, 8):
        eng = E.Engine(api, params,
                       E.ServeConfig(**kw, pool_pages=16,
                                     decode_tile_pages=g), batch=2)
        toks[g] = [r.out_tokens for r in eng.generate(_requests())]
    for g in (2, 4, 8):
        assert toks[g] == toks[1], f"G={g} diverged from one-page schedule"
    assert toks[1] == [r.out_tokens for r in dense]


# ---------------------------------------------------------------------------
# Zero traces under traffic with a warmed ladder
# ---------------------------------------------------------------------------

def test_warmed_ladder_compiles_once_per_bucket(lm):
    api, params = lm
    sc = E.ServeConfig(max_seq=32, kv_compress=True, kv_keep=8,
                       codec_backend="reference", pool_pages=16,
                       aot_warmup=True)
    eng = E.Engine(api, params, sc, batch=2)
    snap = eng.trace_counts.snapshot()
    assert eng.decode_ladder.buckets == (8, 16, 32)
    # one decode trace per ladder bucket, all ahead of traffic
    assert snap["decode"] == len(eng.decode_ladder.buckets)
    # traffic spanning several buckets (deepest context reaches 27 tokens)
    rng = np.random.default_rng(2)
    reqs = [E.Request(uid=i, prompt=rng.integers(0, 200, p).astype(np.int32),
                      max_new=n)
            for i, (p, n) in enumerate([(4, 3), (12, 8), (19, 8)])]
    done = eng.generate(reqs)
    assert all(r.done for r in done)
    assert eng.trace_counts.delta(snap) == {}  # zero compiles under traffic
    buckets_hit = eng.stats["decode_bucket_tokens"]
    assert 0 < buckets_hit < 32 * eng.stats["steps"]  # ladder engaged
