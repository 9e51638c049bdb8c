"""GradCompress unit + property tests (core/grad_comp.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests; see requirements-dev.txt
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import grad_comp as GC


@settings(max_examples=20, deadline=None)
@given(
    rows=st.integers(1, 6),
    cols=st.integers(1, 6),
    keep=st.integers(2, 8),
    seed=st.integers(0, 2**31 - 1),
    mode=st.sampled_from(["topk", "corner"]),
)
def test_leaf_roundtrip(rows, cols, keep, seed, mode):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.standard_normal((rows * 8, cols * 8)).astype(np.float32))
    q, idx, s = GC.compress_leaf(g, keep, mode)
    back = GC.decompress_leaf(q, idx, s, g.shape)
    err = float(jnp.linalg.norm(back - g) / (jnp.linalg.norm(g) + 1e-9))
    assert err < 1.05
    if keep == 8:
        assert err < 0.05


def _ef_run(g_true, keep, mode, steps=40):
    residual = jnp.zeros_like(g_true)
    received = []
    for _ in range(steps):
        g_fb = g_true + residual
        q, idx, s = GC.compress_leaf(g_fb, keep, mode)
        approx = GC.decompress_leaf(q, idx, s, g_true.shape)
        residual = g_fb - approx
        received.append(approx)
    mean_received = jnp.mean(jnp.stack(received), axis=0)
    err = float(jnp.linalg.norm(mean_received - g_true) / jnp.linalg.norm(g_true))
    return err, float(jnp.linalg.norm(residual))


def test_error_feedback_topk_converges_corner_diverges():
    """EF needs a CONTRACTIVE compressor. Magnitude top-k contracts (the
    mean received gradient converges to the truth); the paper's fixed-corner
    projection is idempotent — its residual grows linearly and the mean never
    improves. This pins the refuted-hypothesis log in EXPERIMENTS.md §Perf."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.standard_normal((64, 64)).astype(np.float32))
    q, idx, s = GC.compress_leaf(g_true, 3, "topk")
    one = GC.decompress_leaf(q, idx, s, g_true.shape)
    one_err = float(jnp.linalg.norm(one - g_true) / jnp.linalg.norm(g_true))

    err_topk, resid_topk = _ef_run(g_true, 3, "topk")
    err_corner, resid_corner = _ef_run(g_true, 3, "corner")

    assert err_topk < 0.35 * one_err, (err_topk, one_err)   # ~10x better
    assert err_corner > 0.8 * one_err          # never improves
    assert resid_corner > 10 * resid_topk      # linear blow-up (measured 13x)


def test_exchange_compressed_under_shard_map():
    """2-pod exchange: both pods receive the mean of the per-pod grads.

    The CI multidevice job exercises this case under 4 forced host
    devices."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices (run under XLA_FLAGS device_count)")
    from repro.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh((2,), ("pod",))
    grads = {"w": jnp.stack([jnp.ones((16, 16)), 3 * jnp.ones((16, 16))])}
    residual = {"w": jnp.zeros((16, 16))}
    cfg = GC.GradCompressConfig(keep=8)

    def f(g, r):
        out, new_r = GC.exchange_compressed(g, r, cfg, axis="pod")
        return out, new_r

    from jax.sharding import PartitionSpec as P
    g_local = {"w": grads["w"].reshape(32, 16)}  # (2*16, 16) sharded over pod
    fn = jax.shard_map(
        lambda g, r: f({"w": g["w"]}, r),
        mesh=mesh, in_specs=({"w": P("pod")}, {"w": P()}),
        out_specs=({"w": P("pod")}, {"w": P()}), axis_names={"pod"},
        check_vma=False,
    )
    out, _ = fn(g_local, residual)
    # mean of (1, 3) = 2 everywhere (up to int8 quant)
    np.testing.assert_allclose(np.asarray(out["w"]), 2.0, atol=0.05)


def test_small_leaves_bypass():
    grads = {"bias": jnp.ones((7,)), "big": jnp.ones((64, 64))}
    res = GC.init_residual(grads)
    assert res["bias"].shape == ()       # placeholder
    assert res["big"].shape == (64, 64)


def test_wire_bytes_accounting():
    params = {"w": jnp.zeros((1024, 1024)), "b": jnp.zeros((7,))}
    wb = GC.wire_bytes(params, GC.GradCompressConfig(keep=5))
    # topk: (2*25+4)/64 bytes per tile of 64 f32 = ~0.21 + the raw bias
    assert wb["ratio"] < 0.25
    wb_corner = GC.wire_bytes(params, GC.GradCompressConfig(keep=5, mode="corner"))
    assert wb_corner["ratio"] < 0.13
