"""CPU tests of the reading of the program's own names in a profile
(`chipbench/scopes.py`, `metrics/host_busy_share.py`), on a profile
synthesized as a text proto in the form a v5e trace has: one chip that
runs two decode programs whose ops carry their `op_name` path as the
`tf_op` stat, where control flow (`while`, `conditional`) and the layout
copies the compiler adds carry none, and a host plane whose serve thread
and bookkeeping thread hold `serve.*` and `python.gc` spans.

Timeline (ns; the window is [0, 100000)):

    device   [1000, 41000) decode step 1: while{qkv, cond{copy, tuple,
             compress}, attend, copy}, unembed; [50000, 70000) decode
             step 2: an unscoped copy, while{qkv, cond{copy}};
             [75000, 90000) unembed
    serve    dispatch(1) 500-900, readback(1) 40000-41100, process(1),
             admit 42000-48000 {prefill, readback}, dispatch(2) 48000-49500,
             readback(2) 89000-90050
    worker   python.gc 45200-45800, bookkeeping 91000-99000
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from chipbench import metrics, scopes, trace  # noqa: E402

DECODE = "jit(traced_decode)/layer_scan/while/body/closed_call"
# (metadata id, HLO text, scope path or None, path held as a reference)
OPS = [
    (1, "%while.1 = (s32[]) while((s32[]) %t), condition=%c, body=%b",
     None, False),
    (2, "%fusion.1 = bf16[32,896] fusion(bf16[32,896] %x), kind=kLoop",
     DECODE + "/qkv/dot_general:", False),
    (3, "%cond.31 = (s8[8192,2,8,4,4]) conditional(s32[] %p, (s8[8192,2,8,"
        "4,4]) %t, (s8[8192,2,8,4,4]) %u), branch_computations={%r6, %r7}",
     None, False),
    (4, "%copy.173 = s8[8192,2,8,4,4]{4,3,2,1,0} copy(s8[8192,2,8,4,4]"
        "{2,4,3,1,0} %fusion.1)", None, False),
    (5, "%fused_compress.3 = (s8[1,4,4,64,8], f32[1,64,8]) custom-call("
        "bf16[512,64] %b), custom_call_target=\"tpu_custom_call\"",
     DECODE + "/kv_flush/cond/branch_1_fun/jit(_compress_blocks)/"
     "fused_compress/pallas_call:", True),
    (6, "%attend_paged.7 = f32[32,2,7,64] custom-call(s32[32]{0} %c, "
        "s32[32,128]{1,0} %g), custom_call_target=\"tpu_custom_call\"",
     DECODE + "/attend/attend_paged/pallas_call:", True),
    (7, "%copy.226 = s8[1,8192,2,8,4,4] copy(s8[1,8192,2,8,4,4] %b)",
     "jit(traced_decode)/layer_scan/while/body/dynamic_update_slice:", False),
    (8, "%fusion.2 = bf16[32,896] fusion(bf16[32,896] %y), kind=kLoop",
     DECODE + "/mlp/dot_general:", False),
    (9, "%fusion.3 = bf16[32] fusion(bf16[32,896] %z), kind=kOutput",
     "jit(traced_decode)/unembed/dot_general:", False),
    (10, "%copy.281 = s32[32,256] copy(s32[32,256] %cache_block_table)",
     None, False),
    # a branch's tuple names only the call around the layer
    (11, "%tuple.85 = (s8[8192,2,8,4,4]) tuple(s8[8192,2,8,4,4] %c)",
     DECODE + ":", False),
]
OP_EVENTS = [  # (metadata id, start, end)
    (1, 1000, 40000), (2, 1000, 5000), (3, 5000, 20000), (4, 5000, 14000),
    (11, 14000, 15000), (5, 15000, 20000), (6, 20000, 30000),
    (7, 30000, 35000), (8, 35000, 40000), (9, 40000, 41000),
    (10, 50000, 52000), (1, 52000, 70000), (2, 52000, 60000),
    (3, 60000, 70000), (4, 60000, 70000), (9, 75000, 90000),
]
MODULES = [("jit_traced_decode(1)", 1000, 41000),
           ("jit_traced_decode(2)", 50000, 90000)]
SERVE = [  # (name, start, end, stats)
    ("serve.dispatch", 500, 900, {"step": 1, "live": 4, "bucket": 256}),
    ("serve.readback", 40000, 41100, {"step": 1}),
    ("serve.process", 41100, 42000, {"step": 1}),
    ("serve.admit", 42000, 48000, {"rows": 2, "bucket": 64}),
    ("serve.prefill", 43000, 45000, {"rows": 2, "bucket": 64}),
    ("serve.readback", 45000, 47000, {"step": 1}),
    ("serve.dispatch", 48000, 49500, {"step": 2, "live": 4, "bucket": 256}),
    ("serve.readback", 89000, 90050, {"step": 2}),
]
WORKER = [("python.gc", 45200, 45800, {"generation": 2}),
          ("serve.bookkeeping", 91000, 99000, {})]
START_NS = 1_000_000_000


def _ev(mid, t0, t1, stats=()):
    st = " ".join(f"stats {{ metadata_id: {k} int64_value: {v} }}"
                  for k, v in stats)
    return (f"events {{ metadata_id: {mid} offset_ps: {t0 * 1000} "
            f"duration_ps: {(t1 - t0) * 1000} {st} }}")


def _host_plane():
    names = sorted({n for n, *_ in SERVE + WORKER})
    mid = {n: i + 1 for i, n in enumerate(names)}
    keys = sorted({k for *_, st in SERVE + WORKER for k in st})
    sid = {k: i + 1 for i, k in enumerate(keys)}
    line = lambda i, spans: (
        f'lines {{ id: {i} name: "python" timestamp_ns: 0 '
        + " ".join(_ev(mid[n], a, b, [(sid[k], v) for k, v in st.items()])
                   for n, a, b, st in spans) + " }")
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for n, i in mid.items())
    smeta = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{k}" }} }}' for k, i in sid.items())
    return (f'planes {{ id: 2 name: "/host:CPU" {line(1, SERVE)} '
            f'{line(2, WORKER)} {meta} {smeta} }}')


def _device_plane():
    esc = lambda s: s.replace('"', '\\"')
    meta = []
    refs = []
    for mid, text, path, as_ref in OPS:
        st = ""
        if path is not None and as_ref:
            refs.append((100 + mid, path))
            st = f"stats {{ metadata_id: 1 ref_value: {100 + mid} }}"
        elif path is not None:
            st = f'stats {{ metadata_id: 1 str_value: "{path}" }}'
        meta.append(f'event_metadata {{ key: {mid} value {{ id: {mid} '
                    f'name: "{esc(text)}" {st} }} }}')
    mods = [(50 + i, n, a, b) for i, (n, a, b) in enumerate(MODULES)]
    meta += [f'event_metadata {{ key: {m} value {{ id: {m} name: "{n}" }} }}'
             for m, n, _, _ in mods]
    smeta = ['stat_metadata { key: 1 value { id: 1 name: "tf_op" } }'] + [
        f'stat_metadata {{ key: {i} value {{ id: {i} name: "{p}" }} }}'
        for i, p in refs]
    ops = " ".join(_ev(m, a, b) for m, a, b in OP_EVENTS)
    modules = " ".join(_ev(m, a, b) for m, _, a, b in mods)
    return (f'planes {{ id: 1 name: "/device:TPU:0" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ops} }} '
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {modules} }} '
            + " ".join(meta + smeta) + " }")


def _task_plane():
    return ('planes { id: 3 name: "Task Environment" '
            f'stats {{ metadata_id: 1 uint64_value: {START_NS} }} '
            f'stats {{ metadata_id: 2 uint64_value: {START_NS + 100000} }} '
            'stat_metadata { key: 1 value { id: 1 name: "profile_start_time"'
            ' } } stat_metadata { key: 2 value { id: 2 name: '
            '"profile_stop_time" } } }')


def _write(root: Path, host: bool = True) -> str:
    from jax.profiler import ProfileData

    txt = _device_plane() + (_host_plane() if host else "") + _task_plane()
    d = root / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(txt))
    return str(root)


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    import run

    root = _write(tmp_path_factory.mktemp("with_host"))
    path = trace.find_xplane(root)
    tr = run._trace_context(root, START_NS, START_NS + 100000)
    return path, tr


def test_spans_and_scopes_are_read(profile):
    path, tr = profile
    spans = scopes.host_spans(path)
    assert [(s[0], s[1]) for s in spans] == sorted(
        ((n, float(a)) for n, a, *_ in SERVE + WORKER), key=lambda s: s[1])
    assert [s[3]["step"] for s in spans if s[0] == "serve.dispatch"] == [1, 2]
    assert len({s[4] for s in spans}) == 2
    dev, = scopes.device_ops(path)
    assert dev.name == "/device:TPU:0"
    assert dev.ops == [(m, float(a), float(b - a)) for m, a, b in OP_EVENTS]
    assert all(dev.text[m] == t for m, t, _, _ in OPS)
    # held as a string or as a reference to a stat metadata's name
    assert dev.path == {m: p for m, _, p, _ in OPS if p is not None}
    paths = {t: p for _, t, p, _ in OPS}
    assert scopes.scope_chain(paths[OPS[4][1]]) == ("layer_scan", "kv_flush")
    assert scopes.scope_chain("jit(traced_decode)/gather") == ()


def test_device_scopes_and_flush_share(profile):
    path, tr = profile
    rep = scopes.report(path, tr)
    got = {k: round(v * 1e9) for k, v in rep["device_scopes"]}
    # the unscoped copies inside the flush conditional are kv_flush's, in
    # a run that took the flush branch and in one that did not; the
    # branch's tuple keeps its own, outer scope
    assert got == {"kv_flush": 24000, "unembed": 16000, "qkv": 12000,
                   "attend": 10000, "layer_scan": 6000, "mlp": 5000,
                   scopes.NO_SCOPE: 2000}
    assert [k for k, _ in rep["device_scopes"]][:3] == [
        "kv_flush", "unembed", "qkv"]
    assert rep["flush_share"] == pytest.approx(24.0)
    assert rep["no_scope_share"] == pytest.approx(100 * 2000 / 75000)
    assert sum(got.values()) == trace.busy_ns(tr["planes"][0].ops, 0, 1e5)


def test_idle_gaps_are_named_by_the_covering_span(profile):
    path, tr = profile
    rep = scopes.report(path, tr)
    assert [(lab, round(n * 1e9)) for lab, n in rep["idle_gaps"]] == [
        ("serve.bookkeeping", 10000),  # the serve thread holds no span
        ("python.gc", 9000),           # a collection beats serve.readback
        (scopes.NO_SPAN, 5000),
        ("serve.dispatch", 1000)]
    assert rep["host_spans"]["serve.readback"][0] == 3
    assert rep["host_spans"]["serve.admit"][1] == pytest.approx(6e-6)


def test_readback_lag_skips_admission_readbacks(profile):
    path, tr = profile
    rep = scopes.report(path, tr)
    lag = rep["readback_lag_ms"]
    assert lag["n"] == 2
    assert lag["min"] == pytest.approx(50e-6) \
        and lag["max"] == pytest.approx(100e-6)
    # both readbacks began before their program ended
    assert rep["readback_lag_ms_waiting"] == lag


def test_host_plane_changes_no_existing_reading(profile, tmp_path):
    import run

    _, with_host = profile
    root = _write(tmp_path, host=False)
    without = run._trace_context(root, START_NS, START_NS + 100000)
    # a program without spans (the parent of this reading) reads as such
    rep = scopes.report(trace.find_xplane(root), without)
    edge, none = scopes.WINDOW_EDGE, scopes.NO_SPAN
    assert [lab for lab, _ in rep["idle_gaps"]] == [edge, none, none, edge]
    assert rep["readback_lag_ms"]["n"] == 0 and rep["host_spans"] == {}
    work = {"attn_flops": 1e6, "kv_bytes": 1e6}
    peaks = {"bf16_flops": 1e12, "hbm_bytes_s": 1e11}

    def read_all(tr):
        ctx = {"trace": tr, "work": work, "peaks": peaks, "keep": 4,
               "chips": 1}
        return {m: metrics.load_reader(m)(ctx) for m in (
            "idle_share", "compress_share", "attend_paged_roofline",
            "prefill_share")}, run._breakdown(tr)

    assert read_all(with_host) == read_all(without)
    vals, _ = read_all(with_host)
    # the renamed kernels are still found by their operands and results
    assert vals["compress_share"] == pytest.approx(5.0)
    assert vals["attend_paged_roofline"] == pytest.approx(
        100 * 1e-5 / 10000e-9)


def test_host_busy_share_reads_the_wait_counter():
    read = metrics.load_reader("host_busy_share")
    ctx = {"window_s": 10.0, "stats0": {"wait_s": 1.0},
           "stats1": {"wait_s": 10.5}}
    assert read(ctx) == pytest.approx(5.0)
    # a program without the counter (the parent of this metric) reads None
    assert read({"window_s": 10.0, "stats0": {}, "stats1": {}}) is None
