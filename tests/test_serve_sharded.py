"""Mesh-native serving: the engine on a (data x model) host mesh must be a
pure placement change — greedy tokens bitwise identical to the single-device
engine for compressed (uniform + pyramid plan) and raw caches, including
slot retirement/re-admission — and the decode step must compile shard-local
(no full-cache all-gather in its HLO).

Runs under XLA_FLAGS=--xla_force_host_platform_device_count=4 (the CI
multidevice job sets it); skipped when fewer than 4 devices exist, so the
plain tier-1 invocation is unaffected.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.models import api as model_api
from repro.parallel import mesh as mesh_lib
from repro.parallel import sharding as sh
from repro.serve import engine as E

pytestmark = pytest.mark.slow  # mesh parity: tier1-mesh job only

if len(jax.devices()) < 4:
    pytest.skip(
        "needs 4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)",
        allow_module_level=True)

PLENS = [5, 9, 12, 16, 3, 21, 8, 14]
MAX_NEWS = [3, 7, 5, 9, 4, 6, 8, 5]
PYRAMID = "0-1:keep=8,2-:keep=4"  # 2 segments over the 4 reduced layers
MESHES = ("4x1", "2x2")


@pytest.fixture(scope="module")
def lm():
    api = model_api.build_reduced("yi_6b")
    params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    return api, params


def _requests(n=8, seed=42):
    rng = np.random.default_rng(seed)
    return [E.Request(uid=i, prompt=rng.integers(0, 200, PLENS[i]).astype(np.int32),
                      max_new=MAX_NEWS[i]) for i in range(n)]


def _serve(api, params, sc, batch=4, n=8):
    eng = E.Engine(api, params, sc, batch=batch)
    done = eng.generate(_requests(n))
    assert all(r.done for r in done)
    return [r.out_tokens for r in done], eng


# ---------------------------------------------------------------------------
# Bitwise greedy parity: sharded pool == single device
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_spec", MESHES)
@pytest.mark.parametrize("plan", [8, PYRAMID], ids=["uniform", "pyramid"])
def test_compressed_parity_on_mesh(lm, mesh_spec, plan):
    """8 requests through 4 slots (retirement + re-admission) over the
    compressed pool: per-request greedy outputs must match the single-device
    engine token for token — the mesh is a placement change only."""
    api, params = lm
    kw = dict(max_seq=64, kv_compress=True, plan=plan,
              codec_backend="reference")
    base, _ = _serve(api, params, E.ServeConfig(**kw))
    got, eng = _serve(api, params,
                      E.ServeConfig(**kw, mesh=mesh_lib.make_serve_mesh(mesh_spec)))
    assert eng.scheduler == "continuous"
    assert eng.stats["requests"] == 8  # 8 requests over 4 slots => slot reuse
    assert got == base


@pytest.mark.parametrize("mesh_spec", MESHES)
@pytest.mark.parametrize("plan", [8, PYRAMID], ids=["uniform", "pyramid"])
def test_paged_pool_parity_on_mesh(lm, mesh_spec, plan):
    """The PAGED pool on a mesh (pages + block tables on `data`, heads on
    `model`) must reproduce the single-device dense engine bit for bit,
    through retirement/re-admission and host-side page reuse."""
    api, params = lm
    kw = dict(max_seq=64, kv_compress=True, plan=plan,
              codec_backend="reference")
    base, _ = _serve(api, params, E.ServeConfig(**kw))
    got, eng = _serve(api, params,
                      E.ServeConfig(**kw, pool_pages=24,
                                    mesh=mesh_lib.make_serve_mesh(mesh_spec)))
    assert eng.paged and eng.scheduler == "continuous"
    assert got == base
    assert sorted(eng._free_pages) == list(range(24))  # pool fully drained


@pytest.mark.parametrize("mesh_spec", MESHES)
def test_raw_parity_on_mesh(lm, mesh_spec):
    api, params = lm
    base, _ = _serve(api, params, E.ServeConfig(max_seq=64))
    got, _ = _serve(api, params,
                    E.ServeConfig(max_seq=64,
                                  mesh=mesh_lib.make_serve_mesh(mesh_spec)))
    assert got == base


def test_nondivisible_heads_parity_on_mesh(lm):
    """model=4 with n_kv_heads=2: cache_specs falls back to sharding the
    S/8 block axis on `model`, and the in-step hints must follow the same
    rule (heads-else-blocks) — parity pins the layout against regressions."""
    api, params = lm
    assert api.cfg.n_kv_heads % 4 != 0  # the case under test
    kw = dict(max_seq=64, kv_compress=True, kv_keep=8,
              codec_backend="reference")
    base, _ = _serve(api, params, E.ServeConfig(**kw))
    got, _ = _serve(api, params,
                    E.ServeConfig(**kw, mesh=mesh_lib.make_serve_mesh("1x4")))
    assert got == base


def test_mla_parity_on_mesh():
    """MLA latent cache (c_kv/k_rope leaves) shards on the same rules."""
    api = model_api.build_reduced("deepseek_v2_236b")
    params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    base, _ = _serve(api, params, E.ServeConfig(max_seq=64), n=4)
    got, _ = _serve(api, params,
                    E.ServeConfig(max_seq=64,
                                  mesh=mesh_lib.make_serve_mesh("4x1")), n=4)
    assert got == base


def test_eos_retirement_parity_on_mesh(lm):
    """Mid-stream EOS retires slots and re-admits queued requests: the
    sharded engine must retire/reuse identically (same truncations)."""
    api, params = lm
    kw = dict(max_seq=64, kv_compress=True, kv_keep=8,
              codec_backend="reference")
    probe, _ = _serve(api, params, E.ServeConfig(**kw), batch=2)
    eos = next(t for toks in probe for t in toks[1:-1])
    base, _ = _serve(api, params, E.ServeConfig(**kw, eos_id=eos), batch=2)
    got, eng = _serve(api, params,
                      E.ServeConfig(**kw, eos_id=eos,
                                    mesh=mesh_lib.make_serve_mesh("2x2")),
                      batch=2)
    assert got == base
    assert eng.stats["requests"] == 8


def test_static_scheduler_parity_on_mesh(lm):
    """Wave-at-a-time baseline under a mesh (scalar pos, full-batch prefill)."""
    api, params = lm
    def run(sc):
        eng = E.Engine(api, params, sc, batch=4, scheduler="static")
        return [r.out_tokens for r in eng.generate(_requests())]
    base = run(E.ServeConfig(max_seq=64))
    got = run(E.ServeConfig(max_seq=64, mesh=mesh_lib.make_serve_mesh("4x1")))
    assert got == base


# ---------------------------------------------------------------------------
# Compiled placement: explicit shardings, shard-local decode
# ---------------------------------------------------------------------------

def test_decode_hlo_has_no_full_cache_all_gather(lm):
    """Acceptance criterion: the jitted decode step runs under explicit
    NamedShardings and its optimized HLO never gathers the cache — every
    all-gather (flush-block updates, scatter indices) must be per-token
    sized, independent of max_seq."""
    api, params = lm
    mesh = mesh_lib.make_serve_mesh("4x1")
    sc = E.ServeConfig(max_seq=64, kv_compress=True, kv_keep=8,
                       codec_backend="reference", mesh=mesh)
    eng = E.Engine(api, params, sc, batch=4)
    with jax.set_mesh(mesh):
        cache = eng._cache_init(4)
        zeros = eng.device_vec(np.zeros((4,), np.int32))
        args = (eng.params, zeros, cache, zeros)
        txt = eng._decode.lower(*args).compile().as_text()
    # one segment: packed_k (L, B, ns, Hkv, hd/8, k, k) int8 — the smallest
    # full-cache plane anything could gather
    seg = cache.segments[0]
    plane_bytes = int(np.prod(seg.packed_k.shape))
    gathered = []
    for m in re.finditer(r"all-gather[^=]*= (\w+)\[([\d,]*)\]", txt):
        dtype, dims = m.group(1), m.group(2)
        n = int(np.prod([int(d) for d in dims.split(",")])) if dims else 1
        itemsize = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2,
                    "s8": 1, "u8": 1, "pred": 1}.get(dtype, 4)
        gathered.append((n * itemsize, m.group(0)))
    for nbytes, line in gathered:
        assert nbytes < plane_bytes / 2, (nbytes, plane_bytes, line)


def test_decode_io_shardings_are_explicit(lm):
    """Decode in/out shardings: cache batch slots on data, (B,) vectors on
    data — verified on the compiled executable, not just the spec tree."""
    api, params = lm
    mesh = mesh_lib.make_serve_mesh("4x1")
    sc = E.ServeConfig(max_seq=64, kv_compress=True, kv_keep=8,
                       codec_backend="reference", mesh=mesh)
    eng = E.Engine(api, params, sc, batch=4)
    with jax.set_mesh(mesh):
        cache = eng._cache_init(4)
        zeros = eng.device_vec(np.zeros((4,), np.int32))
        tok, pos1, cache2 = eng._decode(eng.params, zeros, cache, zeros)
    def batch_axis(arr):
        return arr.sharding.spec[1]
    for segment in cache2.segments:
        for name in ("packed_k", "scale_k", "packed_v", "scale_v",
                     "tail_k", "tail_v"):
            spec_entry = batch_axis(getattr(segment, name))
            assert spec_entry in ("data", ("data",)), (name, spec_entry)
    # the fused step's (B,) sampled-token / pos outputs — the only tensors
    # the async loop reads back — ride the data axes like the slots
    for vec in (tok, pos1):
        assert vec.sharding.spec[0] in ("data", ("data",)), vec.sharding.spec


def test_cache_specs_cover_kv_segments(lm):
    """cache_specs dispatches by field name straight off the KVSegment
    pytree (uniform and pyramid plans), and kv_pool_specs builds the same
    tree from (cfg, plan, mesh) alone."""
    from repro.core import kv_cache as KV

    api, params = lm
    cfg = api.cfg
    mesh = mesh_lib.make_serve_mesh("2x2")
    for plan in (8, PYRAMID):
        shapes = jax.eval_shape(
            lambda: KV.init_compressed_cache(cfg, 4, 64, plan=plan))
        specs = sh.cache_specs(shapes, cfg, mesh)
        pool_specs = sh.kv_pool_specs(cfg, plan, mesh, batch=4, max_seq=64)
        assert jax.tree.structure(specs, is_leaf=lambda s: isinstance(s, P)) \
            == jax.tree.structure(pool_specs, is_leaf=lambda s: isinstance(s, P))
        for seg_spec in specs.segments:
            # slots on data; kv heads (2) divide model (2) => head-sharded
            assert seg_spec.packed_k[1] in ("data", ("data",))
            assert seg_spec.packed_k[3] == "model"
            assert seg_spec.tail_k[3] == "model"
        # per-device bytes: data x model both divide their axes => 4x split
        total = sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                    for l in jax.tree.leaves(shapes))
        per_dev = sh.per_device_bytes(shapes, specs, mesh)
        assert per_dev == pytest.approx(total / 4)
