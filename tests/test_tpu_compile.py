"""Compile tests for the main-path Pallas kernels at serving widths, for a
TPU v5e that is described to the TPU compiler, not attached.

Interpret mode cannot see what Mosaic refuses (block shapes off the (8, 128)
tiling, reshapes across it, VMEM overruns); these compiles can, in about a
second each.  The topology is described inside a module fixture, never at
import time: one process at a time may load the TPU library, and every
test worker imports this file.  Nothing here runs on a device.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_attend.kernel import attend_paged
from repro.kernels.fused_compress.kernel import (compress_plane_pallas,
                                                 decompress_plane_pallas)

B, PAGES, MAX_SEQ, G = 8, 1024, 1024, 8
# (head_dim, kv heads, query heads per kv head) of the served GQA models
WIDTHS = {"qwen2_0_5b": (64, 2, 7), "yi_6b": (128, 4, 8)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without a chip: keep it out of any cache for these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("keep", [4, 8])
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_attend_paged_compiles(one_chip, arch, keep):
    hd, hkv, n_rep = WIDTHS[arch]
    nh = hd // 8
    pool = (PAGES, hkv, nh, keep, keep)
    compiled = _compile(
        lambda pk, sk, pv, sv, q, pos, bt, tk, tv: attend_paged(
            pk, sk, pv, sv, q, pos, bt, tk, tv, pages_per_tile=G,
            interpret=False),
        one_chip,
        (pool, jnp.int8), ((PAGES, hkv, nh), jnp.float32),
        (pool, jnp.int8), ((PAGES, hkv, nh), jnp.float32),
        ((B, hkv, n_rep, hd), jnp.bfloat16), ((B,), jnp.int32),
        ((B, MAX_SEQ // 8), jnp.int32),
        ((B, 8, hkv, hd), jnp.bfloat16), ((B, 8, hkv, hd), jnp.bfloat16))
    _assert_kernel(compiled)


def _planes():
    """KV planes the codec sees: a decode flush (B x Hkv x 8 rows) per
    served model, and a 512-token prefill admission of qwen2_0_5b."""
    out = [(B * hkv * 8, hd) for hd, hkv, _ in WIDTHS.values()]
    hd, hkv, _ = WIDTHS["qwen2_0_5b"]
    return out + [(B * hkv * 512, hd)]


@pytest.mark.parametrize("keep", [4, 8])
@pytest.mark.parametrize("plane", _planes(), ids=lambda p: f"{p[0]}x{p[1]}")
def test_compress_plane_compiles(one_chip, plane, keep):
    compiled = _compile(
        lambda x: compress_plane_pallas(x, keep, interpret=False),
        one_chip, (plane, jnp.float32))
    _assert_kernel(compiled)


@pytest.mark.parametrize("keep", [4, 8])
@pytest.mark.parametrize("plane", _planes(), ids=lambda p: f"{p[0]}x{p[1]}")
def test_decompress_plane_compiles(one_chip, plane, keep):
    r, c = plane
    compiled = _compile(
        lambda q, s: decompress_plane_pallas(q, s, interpret=False),
        one_chip, ((r // 8, c // 8, keep, keep), jnp.int8),
        ((r // 8, c // 8), jnp.float32))
    _assert_kernel(compiled)



def test_kernels_carry_their_names(one_chip):
    """Each main-path kernel is an instruction named after it, which is how
    a profile's op events name it."""
    import re

    hd, hkv, n_rep = WIDTHS["qwen2_0_5b"]
    pool = (PAGES, hkv, hd // 8, 4, 4)
    attend = _compile(
        lambda pk, sk, q, pos, bt, t: attend_paged(
            pk, sk, pk, sk, q, pos, bt, t, t, pages_per_tile=G,
            interpret=False),
        one_chip, (pool, jnp.int8), ((PAGES, hkv, hd // 8), jnp.float32),
        ((B, hkv, n_rep, hd), jnp.bfloat16), ((B,), jnp.int32),
        ((B, MAX_SEQ // 8), jnp.int32), ((B, 8, hkv, hd), jnp.bfloat16))
    r, c = _planes()[0]
    codec = _compile(
        lambda x: decompress_plane_pallas(
            *compress_plane_pallas(x, 4, interpret=False), interpret=False),
        one_chip, ((r, c), jnp.float32))
    for name, compiled in (("attend_paged", attend),
                           ("fused_compress", codec),
                           ("fused_decompress", codec)):
        assert re.search(rf"%{name}(\.\d+)? = .*tpu_custom_call",
                         compiled.as_text()), name

def test_qwen2_paged_decode_step_fits_one_v5e(one_chip, monkeypatch):
    """The whole compressed paged decode step of qwen2_0_5b at published
    widths compiles for one v5e and fits its 16 GB with room to spare."""
    from repro.configs.base import get_config
    from repro.models import api as model_api
    from repro.serve import engine as E

    monkeypatch.setenv("REPRO_CODEC_INTERPRET", "0")  # this host is a CPU
    cfg = get_config("qwen2_0_5b")
    api = model_api.build("qwen2_0_5b", cfg)
    sc = E.ServeConfig(max_seq=MAX_SEQ, kv_compress=True, kv_keep=4,
                       codec_backend="pallas", pool_pages=PAGES)
    _, decode_fn, cache_init, _ = E.make_steps(api, sc)
    place = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: cache_init(B)))
    vec = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(decode_fn).lower(params, vec, cache, vec,
                                        vec).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 4 * 2**30, mem  # ~1 GB of bf16 weights + pool + temps
