"""Sharding-rule unit tests + HLO roofline analyzer tests (no big compiles)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.parallel import mesh as mesh_lib
from repro.parallel import sharding as sh
from repro.roofline import analysis as RA
from repro.roofline import hlo as H


@pytest.fixture(scope="module")
def mesh():
    return mesh_lib.make_mesh((1, 1), ("data", "model"))


def test_param_spec_rules(mesh):
    params = {
        "embed": jnp.zeros((256, 64)),
        "layers": {
            "attn": {"wq": {"w": jnp.zeros((4, 64, 64))},
                     "wo": {"w": jnp.zeros((4, 64, 64))}},
            "mlp": {"wu": {"w": jnp.zeros((4, 64, 128))},
                    "wd": {"w": jnp.zeros((4, 128, 64))}},
            "ln1": {"g": jnp.zeros((4, 64))},
        },
        "moe_layers": {"moe": {"wg": jnp.zeros((4, 8, 64, 32)),
                               "router": {"w": jnp.zeros((4, 64, 8))}}},
    }
    def norm(spec):
        # newer jax normalizes 1-tuples to bare names; compare canonically
        return tuple(p[0] if isinstance(p, tuple) and len(p) == 1 else p
                     for p in spec)

    specs = sh.param_specs(params, mesh, fsdp=True)
    assert norm(specs["embed"]) == norm(P("model", ("data",)))
    assert norm(specs["layers"]["attn"]["wq"]["w"]) == norm(P(None, ("data",), "model"))
    assert norm(specs["layers"]["attn"]["wo"]["w"]) == norm(P(None, "model", ("data",)))
    assert norm(specs["layers"]["mlp"]["wd"]["w"]) == norm(P(None, "model", ("data",)))
    assert norm(specs["layers"]["ln1"]["g"]) == norm(P(None, None))
    assert norm(specs["moe_layers"]["moe"]["wg"]) == norm(P(None, "model", ("data",), None))
    assert norm(specs["moe_layers"]["moe"]["router"]["w"]) == norm(P(None, ("data",), None))


def test_fit_spec_divisibility():
    mesh = mesh_lib.make_mesh((2, 2, 2), ("pod", "data", "model")) \
        if len(jax.devices()) >= 8 else None
    if mesh is None:
        pytest.skip("needs 8 devices")
    # batch 1 cannot shard over ("pod","data")
    assert sh.fit_spec(P(("pod", "data")), (1,), mesh) == P(None)
    # batch 2 shards over pod only
    assert sh.fit_spec(P(("pod", "data")), (2,), mesh) == P("pod")
    # odd vocab cannot shard over model
    assert sh.fit_spec(P("model", None), (51865, 512), mesh) == P(None, None)
    assert sh.fit_spec(P("model", None), (512, 64), mesh) == P("model", None)


def test_cache_specs_dispatch(mesh):
    from repro.configs.base import get_config

    cfg = get_config("yi_6b")
    cache = {
        "k": jax.ShapeDtypeStruct((32, 8, 64, 4, 128), jnp.bfloat16),
        "v": jax.ShapeDtypeStruct((32, 8, 64, 4, 128), jnp.bfloat16),
    }
    specs = sh.cache_specs(cache, cfg, mesh)
    assert len(specs["k"]) == 5


def test_cache_specs_dispatch_on_kv_segments(mesh):
    """The KVSegment pytree registers key paths, so the same name-dispatch
    rules cover the serve engine's CompressedKVCache directly — including
    per-policy (pyramid) plans with one spec set per segment."""
    from repro.configs.base import get_config
    from repro.core import kv_cache as KV

    cfg = get_config("yi_6b").reduced()
    shapes = jax.eval_shape(
        lambda: KV.init_compressed_cache(cfg, 4, 64, plan="0-1:keep=8,2-:keep=4"))
    specs = sh.cache_specs(shapes, cfg, mesh)
    assert len(specs.segments) == 2
    for seg_shapes, seg_spec in zip(shapes.segments, specs.segments):
        assert len(seg_spec.packed_k) == seg_shapes.packed_k.ndim == 7
        assert len(seg_spec.tail_k) == seg_shapes.tail_k.ndim == 5
    # kv_pool_specs builds the identical tree from (cfg, plan, mesh) alone
    pool = sh.kv_pool_specs(cfg, "0-1:keep=8,2-:keep=4", mesh, batch=4,
                            max_seq=64)
    assert specs == pool


def test_per_device_bytes_counts_shard_factors(mesh):
    shapes = {"a": jax.ShapeDtypeStruct((8, 16), jnp.float32),
              "b": jax.ShapeDtypeStruct((4,), jnp.int8)}
    specs = {"a": P(("data",), "model"), "b": P(None)}
    # 1x1 module mesh: factors are 1 -> exact byte total
    assert sh.per_device_bytes(shapes, specs, mesh) == 8 * 16 * 4 + 4


def test_make_serve_mesh_spec_parsing():
    assert mesh_lib.parse_mesh_spec("4x1") == (4, 1)
    assert mesh_lib.parse_mesh_spec("2X2") == (2, 2)
    assert mesh_lib.make_serve_mesh(None) is None
    assert mesh_lib.make_serve_mesh("") is None
    with pytest.raises(ValueError):
        mesh_lib.parse_mesh_spec("4")
    with pytest.raises(ValueError):
        mesh_lib.parse_mesh_spec("0x2")
    n = len(jax.devices())
    with pytest.raises(ValueError):
        mesh_lib.make_serve_mesh(f"{n + 1}x1")
    m = mesh_lib.make_serve_mesh(f"{n}x1")
    assert tuple(m.axis_names) == ("data", "model")
    assert m.shape["data"] == n


def test_launch_mesh_is_a_reexport():
    from repro.launch import mesh as launch_mesh
    from repro.parallel import mesh as parallel_mesh

    assert launch_mesh.make_production_mesh is parallel_mesh.make_production_mesh


def test_hlo_type_bytes():
    assert H._type_bytes("f32[4,8]") == 128
    assert H._type_bytes("bf16[10]{0}") == 20
    assert H._type_bytes("(f32[2], s8[3])") == 11
    assert H._type_bytes("pred[]") == 1  # scalars: dims empty -> 1 elem


def test_hlo_dot_flops():
    types = {"%a": "f32[16,32]", "%b": "f32[32,8]"}
    line = "%dot = f32[16,8]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}"
    assert H._dot_flops(line, "f32[16,8]", types) == 2 * 16 * 8 * 32


def test_hlo_while_trip_multiplication():
    text = """
HloModule test

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[8,8]) tuple(%i, %d)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%z, %a)
  %w = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""
    st = H.analyze(text)
    assert st.flops == 5 * 2 * 8 * 8 * 8


def test_collective_wire_model():
    text = """
HloModule t

ENTRY %main (a: f32[64,64]) -> f32[64,64] {
  %a = f32[64,64]{1,0} parameter(0)
  %ar = f32[64,64]{1,0} all-reduce(%a), replica_groups={}, to_apply=%add
  ROOT %ag = f32[64,64]{1,0} all-gather(%ar), dimensions={0}
}
"""
    st = H.analyze(text)
    assert st.coll["all-reduce"]["wire_bytes"] == 2 * 64 * 64 * 4
    # ag result==operand sizes here -> wire 0 by (res - ops); fine as a parse test
    assert st.coll["all-gather"]["count"] == 1


def test_roofline_terms_and_dominance():
    r = RA.Roofline(
        arch="x", shape="train_4k", mesh="16x16", chips=256,
        device_kind="TPU v5 lite", hlo_flops=197e12, hlo_bytes=819e9 * 2, wire_bytes=50e9 * 0.5,
        model_flops_global=197e12 * 256 * 0.5,
    )
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(2.0)
    assert r.collective_s == pytest.approx(0.5)
    assert r.dominant == "memory"
    assert r.roofline_fraction == pytest.approx(2.0 / 3.5)
    assert r.useful_flop_ratio == pytest.approx(0.5)


def test_roofline_peaks_are_keyed_by_device_kind():
    assert RA.chip_peaks("TPU v5 lite").hbm_bw == 819e9
    r = RA.Roofline(arch="x", shape="train_4k", mesh="1", chips=1,
                    device_kind="cpu", hlo_flops=1.0, hlo_bytes=1.0,
                    wire_bytes=0.0, model_flops_global=1.0)
    with pytest.raises(KeyError, match="cpu"):
        r.compute_s
    with pytest.raises(KeyError, match="cpu"):
        RA.hbm_bandwidth_row(1.0, device_kind="cpu")


def test_model_flops_kinds():
    from repro.configs.base import get_config

    cfg = get_config("yi_6b")
    tr = RA.model_flops(cfg, "train_4k")
    pf = RA.model_flops(cfg, "prefill_32k")
    dc = RA.model_flops(cfg, "decode_32k")
    assert tr > pf > dc
    assert tr == pytest.approx(6 * cfg.param_counts()["active"] * 256 * 4096)
