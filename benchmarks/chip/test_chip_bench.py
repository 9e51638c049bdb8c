"""CPU tests of the chip benchmark's own code (no chip needed).

`testdata/cpu_spans.xplane.pb` was recorded on the CPU with
`jax.profiler` (python tracer off, host tracer at level 1): a 20 ms span
`bench_a`, 30 ms of nothing, then a 10 ms span `bench_b` holding a nested
5 ms span `bench_c`.

The tiny cell (`testdata/tiny.json`, a two-layer decoder of the Qwen2
layout) drives the whole of `run.run_cell` on the CPU, past the harness's
look for a chip: once sound, where the program's reading must lie under
its limit and the reference's float8 control's above it, and once per
fault planted in the timed decode step, where `correct` must come out
false.  Its siblings run the same way with raw K/V (`tiny_rawkv`), on a
2x2 mesh of four virtual devices (`tiny_mesh`, in a child process), and
with a block, configuration and workload that live only under
`testdata/` (`tiny_ln_relu2`).
"""
from __future__ import annotations

import json
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
TESTDATA = BENCH / "testdata"
sys.path.insert(0, str(BENCH))

from chipbench import flops, spec, trace, traffic, weights  # noqa: E402

E2E = [{"name": "tok_s", "unit": "tokens/s"}, {"name": "itl_p95_ms", "unit": "ms"},
       {"name": "setup_s", "unit": "s"}]
LLAMA = spec.load_block("llama")


def bench_json():
    return spec.load_json(ROOT / "BENCHMARK.json")


def config_dims(name: str) -> dict:
    config = spec.load_json(spec.CONFIG_DIR / f"{name}.json")
    return spec.load_block(config["block"]).model_dims(config)


# ---------------------------------------------------------------------------
# Trace reduction
# ---------------------------------------------------------------------------

def test_trace_reduction_on_recorded_cpu_trace():
    path = str(TESTDATA / "cpu_spans.xplane.pb")
    planes = trace.read_planes(path, prefix="/host:CPU", ops_line="python")
    assert [p.name for p in planes] == ["/host:CPU"]
    evs = planes[0].ops
    by = {n: d for n, _, d in evs}
    assert set(by) == {"bench_a", "bench_b", "bench_c"}
    assert 20e6 <= by["bench_a"] < 25e6
    assert 15e6 <= by["bench_b"] < 20e6 and 5e6 <= by["bench_c"] < 8e6
    t0, t1 = trace.window(path, planes)
    assert t0 == 0.0 and t1 >= max(s + d for _, s, d in evs)
    # bench_c lies inside bench_b: the union counts it once
    assert trace.busy_ns(evs) == pytest.approx(by["bench_a"] + by["bench_b"])
    gaps = trace.idle_gaps(evs, t0, t1)
    assert 30e6 <= gaps[0] < 35e6
    assert sum(gaps) + trace.busy_ns(evs) == pytest.approx(t1 - t0)
    assert trace.time_by_name(evs)["bench_c"] == by["bench_c"]


def test_trace_reduction_on_device_plane(tmp_path):
    from jax.profiler import ProfileData

    ev = lambda m, t0, d: (f"events {{ metadata_id: {m} offset_ps: {t0 * 1000} "
                           f"duration_ps: {d * 1000} }}")
    meta = lambda m, n: f'event_metadata {{ key: {m} value {{ id: {m} name: "{n}" }} }}'
    txt = f"""planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
        {ev(1, 1000, 2000)} {ev(2, 2000, 2000)} {ev(1, 10000, 1000)} }}
      lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {ev(3, 1000, 3000)} }}
      lines {{ id: 3 name: "Steps" timestamp_ns: 0 {ev(3, 0, 20000)} }}
      {meta(1, "fusion.3")} {meta(2, "attend_paged.1")}
      {meta(3, "jit_traced_prefill(7)")} }}
    planes {{ id: 2 name: "/host:CPU" }}"""
    path = tmp_path / "device.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(txt))
    planes = trace.read_planes(str(path))
    assert len(planes) == 1 and len(planes[0].ops) == 3
    ops = planes[0].ops
    assert trace.window(str(path), planes) == (1000.0, 11000.0)
    assert trace.busy_ns(ops) == 4000.0          # [1000, 4000) + [10000, 11000)
    assert trace.busy_ns(ops, 2500.0, 10500.0) == 2000.0
    assert trace.idle_gaps(ops, 0.0, 12000.0) == [6000.0, 1000.0, 1000.0]
    assert trace.time_by_name(ops) == {"fusion.3": 3000.0,
                                       "attend_paged.1": 2000.0}
    assert planes[0].modules == [("jit_traced_prefill(7)", 1000.0, 3000.0)]


# HLO texts of op events from a v5e trace of qwen2_0_5b.decode-heavy,
# operand lists cut short
ATTEND_TEXT = (
    '%closed_call.27 = f32[32,2,7,64]{3,2,1,0:T(8,128)S(1)} custom-call('
    's32[32]{0:T(128)S(1)} %copy-done.10, s32[32,128]{1,0:T(8,128)S(1)} '
    '%get-tuple-element.1053, s8[8192,2,8,4,4]{4,3,2,1,0:T(4,128)(4,1)} '
    '%get-tuple-element.931), custom_call_target="tpu_custom_call"')
COMPRESS_TEXT = (
    '%_compress_blocks.3 = (s8[1,4,4,64,8]{4,3,2,1,0:T(8,128)(4,1)S(1)}, '
    'f32[1,64,8]{2,1,0:T(8,128)S(1)}) custom-call(bf16[512,64]{1,0:T(8,128)'
    '(2,1)S(1)} %bitcast.265, f32[64,128]{1,0:T(8,128)} %constant.0), '
    'custom_call_target="tpu_custom_call"')
WHILE_TEXT = ('%while.3 = (s32[]{:T(128)}, bf16[32,1,896]{2,0,1:T(8,128)(2,1)'
              'S(1)}) while((s32[]{:T(128)}, bf16[32,1,896]) %tuple.98), '
              'condition=%region_13.22, body=%region_1.21')
COPY_TEXT = ('%copy.173 = s8[24,8192,2,8,4,4]{5,4,3,2,1,0:T(4,128)(4,1)} '
             'copy(s8[24,8192,2,8,4,4]{1,5,4,3,2,0:T(4,128)(4,1)} %p.3)')


def test_kernels_are_told_by_their_hlo():
    from chipbench import kernels

    cases = [(ATTEND_TEXT, "closed_call.27", "custom-call", True, False),
             (COMPRESS_TEXT, "_compress_blocks.3", "custom-call", False, True),
             (WHILE_TEXT, "while.3", "while", False, False),
             (COPY_TEXT, "copy.173", "copy", False, False)]
    for text, op, code, attend, compress in cases:
        assert trace.op_name(text) == op and trace.opcode(text) == code
        assert kernels.is_attend_paged(op, text) is attend
        assert kernels.is_compress(op, text) is compress
    assert kernels.label("closed_call.27", ATTEND_TEXT).startswith("attend_paged")
    assert kernels.label("copy.173", COPY_TEXT) == "copy copy.173"
    assert trace.op_name("bench_a") == "bench_a" and trace.opcode("bench_a") == ""


# ---------------------------------------------------------------------------
# Operations and bytes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,per_token", [("qwen2_0_5b", 1920),
                                            ("yi_6b", 10240)])
def test_kv_bytes_match_the_plan(name, per_token):
    sys.path.insert(0, str(ROOT / "src"))
    from repro.codec import plan as plan_lib
    from repro.configs.base import get_config

    dims = config_dims(name)
    page = plan_lib.as_plan(None, keep=4).page_bytes(get_config(name))
    assert flops.page_bytes(dims, 4) == page == 8 * per_token


def test_flop_count_by_hand():
    d = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 2, "d_ff": 16, "vocab": 10}
    # per layer: q 8x8, k and v 8x4 each, o 8x8, gate/up/down 3 x 8x16
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert LLAMA.matmul_params(d) == 2 * per_layer + 8 * 10
    # one query over 5 positions: 4 * 5 * heads * head_dim per layer
    assert LLAMA.attn_flops(d, 5) == 2 * 4 * 5 * 4 * 2
    assert flops.token_flops(LLAMA, d, 5) == 2 * (2 * per_layer + 80) + 320
    # a prompt of 3 admitted in the window, then decode tokens 1 and 2 at
    # positions 3 and 4 (no pool page read below position 8)
    w = flops.window_work(LLAMA, d, 4, [(3, 0, 3)])
    want = sum(flops.token_flops(LLAMA, d, c) for c in (1, 2, 3, 4, 5))
    assert w["model_flops"] == want
    assert w["attn_flops"] == LLAMA.attn_flops(d, 4) + LLAMA.attn_flops(d, 5)
    assert w["kv_bytes"] == 0
    # a decode token at position 17 reads its two flushed pages
    w = flops.window_work(LLAMA, d, 4, [(10, 8, 9)])
    assert w["kv_bytes"] == 2 * flops.page_bytes(d, 4)
    # raw K/V: its 18 positions, K and V of 2 layers x 2 heads x 2 bf16
    w = flops.window_work(LLAMA, d, None, [(10, 8, 9)])
    assert w["kv_bytes"] == 18 * 2 * 2 * 2 * 2 * 2


def test_raw_kv_bytes_match_the_program_cache():
    """Raw K/V a position: 2 * layers * kv heads * head_dim * 2 bytes, as
    the program's raw cache holds them (12,288 B for qwen2_0_5b)."""
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    cell = spec.load_cell("qwen2_0_5b.decode-heavy.rawkv")
    api = cell.block.program(cell.config)
    seq = 64
    cache = jax.eval_shape(lambda: api.init_cache(1, seq))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert flops.raw_position_bytes(cell.dims) == held // seq == 12288


# ---------------------------------------------------------------------------
# The llama block: pinned to what the harness read before blocks were
# modules (weights bitwise, counts exactly; the reference's float32 logits
# to within what another build of XLA:CPU may round differently)
# ---------------------------------------------------------------------------

PINNED_WEIGHTS = {
    "tied, q/k/v biases":
        "19ef7fa6cedac72188c4d610eaa9e46bdff0ef93181e23956d4be8071ab2bb5a",
    "untied, no biases":
        "159d8017ca9c64e2aa2ddf9227187c99a27fa77cb44dc874549a47a483a368c3",
}
PINNED_SPANS = [(161, 0, 40), (256, 100, 300), (42, 5, 1211), (8, 0, 1)]
PINNED_WORK = {   # window_work at keep 4 over PINNED_SPANS, weight_bytes
    "qwen2_0_5b": ({"model_flops": 1671450955776, "attn_flops": 75819319296,
                    "kv_bytes": 1679907840}, 987922432),
    "yi_6b": ({"model_flops": 19186955321344, "attn_flops": 462136803328,
               "kv_bytes": 8959508480}, 11597250560),
}
PINNED_LOGITS = {  # sum, three logits, greedy rows 16..39 of a 40-token run
    "reference": (-0.0896139144897461,
                  [-0.1287555992603302, -0.17070049047470093,
                   -0.30286532640457153],
                  [127, 62, 223, 62, 127, 62, 175, 235, 164, 65, 235, 158,
                   235, 51, 75, 175, 82, 219, 155, 27, 238, 225, 150, 225]),
    "control": (0.71136474609375,
                [-0.14898681640625, -0.10674667358398438,
                 -0.35135650634765625],
                [127, 62, 223, 62, 127, 62, 175, 235, 164, 66, 235, 158,
                 106, 51, 75, 175, 82, 219, 155, 133, 164, 225, 150, 225]),
}


def _tree_hash(params) -> str:
    import hashlib

    import jax

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("form", sorted(PINNED_WEIGHTS))
def test_llama_block_weights_are_pinned(form):
    d = load_test_cell("tiny").dims
    if form.startswith("untied"):
        d = dict(d, tied=False, qkv_bias=False)
    params = weights.make_params(LLAMA.layout(d), 2 ** 40 + 3)
    assert _tree_hash(params) == PINNED_WEIGHTS[form]


@pytest.mark.parametrize("name", sorted(PINNED_WORK))
def test_llama_block_work_is_pinned(name):
    d = config_dims(name)
    work, wbytes = PINNED_WORK[name]
    assert flops.window_work(LLAMA, d, 4, PINNED_SPANS) == work
    assert flops.weight_bytes(LLAMA, d) == wbytes


@pytest.mark.parametrize("precision", sorted(PINNED_LOGITS))
def test_llama_block_reference_is_pinned(precision):
    import jax.numpy as jnp

    from chipbench import reference

    d = load_test_cell("tiny").dims
    params = weights.make_params(LLAMA.layout(d), 2 ** 40 + 3)
    toks = np.zeros(reference.pad_len(40), np.int32)
    toks[:40] = np.random.default_rng(5).integers(0, 256, 40)
    dkey = tuple(sorted(d.items()))
    h = LLAMA.final_hidden(params, jnp.asarray(toks), 17, d=dkey, keep=4,
                           precision=precision)[16:40]
    lg = np.asarray(LLAMA.logits_rows(params, h, d=dkey, precision=precision))
    total, some, greedy = PINNED_LOGITS[precision]
    assert lg.argmax(-1).tolist() == greedy
    np.testing.assert_allclose(lg[[0, 5, 23], [3, 100, 255]], some,
                               rtol=1e-5, atol=1e-6)
    assert float(lg.sum()) == pytest.approx(total, abs=1e-4)


# ---------------------------------------------------------------------------
# Loading cells: plain errors
# ---------------------------------------------------------------------------

def _write_cell(tmp_path, *, block="llama", serve=None, chips=1, drop=()):
    config = spec.load_json(TESTDATA / "tiny_config.json")
    if block is None:
        config.pop("block")
    else:
        config["block"] = block
    work = spec.load_json(TESTDATA / "tiny.json")
    work["chips"] = chips
    work["serve"].update(serve or {})
    for key in drop:
        del work["serve"][key]
    (tmp_path / "tiny_config.json").write_text(json.dumps(config))
    (tmp_path / "cell.json").write_text(json.dumps(work))


@pytest.mark.parametrize("case,kw,says", [
    ("unknown block", {"block": "no_such_block"}, "unknown block 'no_such_block'"),
    ("no block", {"block": None}, "names no block"),
    ("path for a block", {"block": "../run"}, "is not a name"),
    ("four chips without a mesh", {"chips": 4}, "gives serve.mesh"),
    ("mesh of two chips", {"chips": 4, "serve": {"mesh": "2x1"}},
     "gives serve.mesh"),
    ("one chip with a mesh", {"serve": {"mesh": "2x2"}}, "has no serve.mesh"),
    ("compressed without a keep", {"drop": ("keep",)}, "gives serve.keep"),
])
def test_malformed_cell_is_a_plain_error(tmp_path, case, kw, says):
    _write_cell(tmp_path, **kw)
    with pytest.raises(ValueError, match=re.escape(says)):
        spec.load_cell("cell", tmp_path, tmp_path)


def test_block_without_its_functions_is_a_plain_error(tmp_path):
    (tmp_path / "half.py").write_text("def model_dims(config):\n    return {}\n")
    with pytest.raises(ValueError, match="does not define"):
        spec.load_block("half", tmp_path)


# ---------------------------------------------------------------------------
# Traffic and the cells' files
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", [w["name"] for w in bench_json()["workloads"]])
def test_traffic_is_seeded_and_clipped(cell):
    t = spec.load_cell(cell).traffic
    a = traffic.lengths(t)
    n = t["round_size"]
    for r in range(t["rounds"]):  # every round carries the same mix
        assert sorted(a[r * n:(r + 1) * n]) == sorted(traffic.length_round(t))
    for pl, mn in a:
        assert t["prompt"]["min"] <= pl <= t["prompt"]["max"]
        assert t["output"]["min"] <= mn <= t["output"]["max"]
    p1 = traffic.prompts(t, 2 ** 33 + 5, 1000)[:4]
    p2 = traffic.prompts(t, 2 ** 33 + 5, 1000)[:4]
    assert all(np.array_equal(x, y) and m == k for (x, m), (y, k) in zip(p1, p2))
    # another seed: other token ids, the same lengths in the same order
    p3 = traffic.prompts(t, 7, 1000)[:4]
    assert [(len(x), m) for x, m in p3] == [(len(x), m) for x, m in p1]
    assert not all(np.array_equal(x, y) for (x, _), (y, _) in zip(p1, p3))


@pytest.mark.parametrize("cell", [w["name"] for w in bench_json()["workloads"]])
def test_traffic_has_the_published_means(cell):
    t = spec.load_cell(cell).traffic
    n = t["round_size"]
    for side in ("prompt", "output"):
        p = t[side]
        assert "mean" in p and str(p["mean"]) in t["source"]
        got = traffic.lognormal_quantiles(p, n).mean()
        assert abs(got - p["mean"]) <= 0.01 * p["mean"]
        # the same quantiles, given by the solved median
        median = traffic.solve_median(p, n)
        assert np.array_equal(traffic.quantiles_at(median, p, n),
                              traffic.lognormal_quantiles(p, n))


def test_every_cell_names_its_files():
    bench = bench_json()
    assert bench["paths"] == ["benchmarks/chip"]
    for c in bench["configs"]:
        cfg = spec.load_json(ROOT / c["file"])
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    names = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.workload["config"] == w["config"] in names
        assert cell.workload["chips"] == w["chips"]
        assert cell.workload["why"] == w["why"]
        sv = cell.serve
        # no request's page horizon can exceed the pool, and every prompt
        # fits the prefill ladder: no operation fails on this traffic
        t = cell.traffic
        if cell.keep is not None:
            horizon = (t["prompt"]["max"] + t["output"]["max"] - 1) // 8
            assert horizon <= sv["pool_pages"]
        assert t["prompt"]["max"] <= max(sv["prefill_buckets"]) <= sv["max_seq"]
        assert t["prompt"]["max"] + t["output"]["max"] <= sv["max_seq"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("name", ["qwen2_0_5b", "yi_6b"])
def test_config_files_match_the_program_registry(name):
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs.base import get_config

    d = config_dims(name)
    c = get_config(name)
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads,
            c.resolved_head_dim, c.d_ff, c.vocab_size, c.qkv_bias,
            c.tie_embeddings) == (
        d["n_layers"], d["d_model"], d["n_heads"], d["n_kv_heads"],
        d["head_dim"], d["d_ff"], d["vocab"], d["qkv_bias"], d["tied"])


def _layouts_agree(name):
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    cell = load_test_cell(name)
    leaves = cell.block.layout(cell.dims)
    ours = jax.eval_shape(lambda: weights._make(weights.base_key(2 ** 40 + 3), leaves))
    theirs = jax.eval_shape(
        lambda: cell.block.program(cell.config).init(jax.random.PRNGKey(0)))
    shape = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    assert shape(ours) == shape(theirs)


def test_weights_have_the_program_layout():
    _layouts_agree("tiny")


def test_block_of_testdata_has_the_program_layout():
    _layouts_agree("tiny_ln_relu2")


def test_reference_codec_matches_the_program_codec():
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp

    from chipbench import reference
    from repro.core import kv_cache as kvc

    x = jax.random.normal(jax.random.PRNGKey(0), (32, 2, 16), jnp.float32)
    ours = reference.codec_roundtrip(x, 4)
    planes = jnp.swapaxes(x, 0, 1)                           # (H, S, hd)
    q, s = kvc.compress_kv_blocks(planes, 4, backend="reference")
    theirs = jnp.swapaxes(kvc.decompress_kv_blocks(q, s, jnp.float32,
                                                   backend="reference"), 0, 1)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               atol=1e-5 * float(jnp.abs(x).max()))


# ---------------------------------------------------------------------------
# The harness end to end on the CPU
# ---------------------------------------------------------------------------

def test_run_refuses_without_a_chip():
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "qwen2_0_5b.decode-heavy", "--seed", str(2 ** 31 + 9),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no tpu" in p.stderr


def load_test_cell(name: str):
    """A cell of `testdata/`; `tiny_ln_relu2`'s block is `testdata/`'s too."""
    blocks = TESTDATA / "blocks" if name == "tiny_ln_relu2" else spec.BLOCK_DIR
    return spec.load_cell(name, TESTDATA, TESTDATA, blocks)


def _tiny_run(fault=None, control=False, seed=2 ** 31 + 77, name="tiny"):
    sys.path.insert(0, str(ROOT / "src"))
    import run

    return run.run_cell(load_test_cell(name), seed, 1.0, False,
                        end_to_end=E2E, per_layer=[],
                        t_start=time.perf_counter(), platform="cpu",
                        fault=fault, control=control, log=lambda m: None)


def _sound_and_control(name):
    out, read = _tiny_run(name=name)
    assert out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] == read["logit_gap"]
    assert set(out["metrics"]) == {m["name"] for m in E2E}
    assert list(out)[-1] == "checks"
    json.dumps(out)
    # the control in the program's place: the harness's own comparison
    # must come out as not correct
    out, read = _tiny_run(control=True, name=name)
    limit = out["checks"]["logit_gap"]["limit"]
    assert not out["correct"], (out["checks"], read)
    assert out["checks"]["logit_gap"]["value"] == read["control_logit_gap"]
    assert read["logit_gap"] <= limit < read["control_logit_gap"]
    assert out["failed"] >= 1


def test_sound_run_is_correct_and_control_is_not():
    _sound_and_control("tiny")


def test_rawkv_run_is_correct_and_control_is_not():
    assert load_test_cell("tiny_rawkv").keep is None
    _sound_and_control("tiny_rawkv")


def test_block_of_testdata_alone_runs_and_its_control_does_not():
    """A configuration that is new files only: block, configuration and
    workload all under `testdata/`, none of them known to the harness."""
    cell = load_test_cell("tiny_ln_relu2")
    assert Path(cell.block.__file__).parent == TESTDATA / "blocks"
    assert cell.dims["norm_eps"] == cell.config["config"]["layer_norm_eps"]
    _sound_and_control("tiny_ln_relu2")


def test_a_block_imported_by_another_is_one_module():
    """The package's blocks are its modules: a block outside the package
    that imports one shares it (and its jit caches) with the harness."""
    from chipbench.blocks import llama

    assert LLAMA is llama
    assert load_test_cell("tiny_ln_relu2").block.attention is llama.attention


MESH_RUN = """
import json, sys, time
sys.path[:0] = [{bench!r}, {src!r}]
import jax
import run
from chipbench import spec
cell = spec.load_cell("tiny_mesh", spec.BENCH_DIR / "testdata",
                      spec.BENCH_DIR / "testdata")
out, read = run.run_cell(cell, {seed}, 1.0, False, end_to_end=[],
                         per_layer=[], t_start=time.perf_counter(),
                         platform="cpu", log=lambda m: None)
print(json.dumps(out))
"""


def test_mesh_run_is_correct_on_four_devices():
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = MESH_RUN.format(bench=str(BENCH), src=str(ROOT / "src"),
                           seed=2 ** 33 + 17)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"], out["checks"]


class _Req:
    def __init__(self, uid, n):
        self.uid, self.out_tokens = uid, list(range(n))


def test_sample_takes_the_longest_and_both_halves():
    from chipbench import check

    batch = 8
    finished = [_Req(u, 10 + u) for u in range(40)]
    slot_of = {u: u % batch for u in range(40)}
    picked = check.sample(finished, 2 ** 31 + 3, slot_of, batch, 3, 16)
    assert picked[0] == (finished[-1], 49)      # the longest, in full
    assert all(n == 16 for _, n in picked[1:])  # the others capped
    assert check.halves(picked, slot_of, batch) == (3, 4)
    assert len({r.uid for r, _ in picked}) == len(picked) == 7
    other = check.sample(finished, 5, slot_of, batch, 3, 16)
    assert [r.uid for r, _ in other] != [r.uid for r, _ in picked]
    # a half with too few finished requests shows in the count
    lopsided = {u: 0 for u in range(40)}
    picked = check.sample(finished, 5, lopsided, batch, 3, 16)
    assert check.halves(picked, lopsided, batch) == (4, 0)


@pytest.mark.parametrize("fault", ["token", "state", "half_batch"])  # run.FAULTS
def test_planted_fault_is_not_correct(fault):
    out, read = _tiny_run(fault=fault)
    assert not out["correct"]
    assert read["logit_gap"] > out["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("fault", ["token", "state", "half_batch"])  # run.FAULTS
def test_planted_fault_is_not_correct_with_raw_kv(fault):
    out, read = _tiny_run(fault=fault, name="tiny_rawkv")
    assert not out["correct"]
    assert read["logit_gap"] > out["checks"]["logit_gap"]["limit"]
