"""The serve engine's host spans and the counters they define, read back
from a profile recorded on the CPU.

  * `serve.*` spans nest as the engine's docstring says and carry the
    decode step they belong to;
  * stats["wait_s"] / ["decode_s"] / ["prefill_s"] are the summed
    durations of their spans;
  * recording a profile leaves greedy tokens unchanged;
  * `python.gc` names a collection only while `GcSpans` is installed;
  * the decode and admission programs carry the named device scopes.
"""
import gc
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.models import api as model_api
from repro.serve import engine as E
from repro.serve import pipeline as pl

PLENS = [5, 9, 12, 16, 3, 21, 8, 14]
MAX_NEWS = [3, 7, 5, 9, 4, 6, 8, 5]


@pytest.fixture(scope="module")
def lm():
    api = model_api.build_reduced("yi_6b")
    params = api.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    return api, params


def _engine(lm, warm=True):
    api, params = lm
    sc = E.ServeConfig(max_seq=64, kv_compress=True, kv_keep=8,
                       codec_backend="reference", pool_pages=32,
                       aot_warmup=warm)
    return E.Engine(api, params, sc, batch=4)


def _requests():
    rng = np.random.default_rng(7)
    return [E.Request(uid=i, prompt=rng.integers(0, 200, p).astype(np.int32),
                      max_new=m) for i, (p, m) in enumerate(zip(PLENS,
                                                                MAX_NEWS))]


def _spans(trace_dir, prefixes=("serve.", "python.")):
    """(name, start_ns, end_ns, stats, line) of the program's host spans."""
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for pl_ in ProfileData.from_file(path).planes:
        if pl_.name != "/host:CPU":
            continue
        for i, line in enumerate(pl_.lines):
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats), i)
                       for e in line.events if e.name.startswith(prefixes))
    return sorted(out, key=lambda s: s[1])


def _inside(span, outer):
    return outer[1] <= span[1] and span[2] <= outer[2] and span[4] == outer[4]


@pytest.fixture(scope="module")
def traced(lm, tmp_path_factory):
    eng = _engine(lm)
    reqs = _requests()
    d = tmp_path_factory.mktemp("serve_trace")
    stats0 = dict(eng.stats)
    with jax.profiler.trace(str(d)):
        eng.generate(reqs)
    return reqs, _spans(d), stats0, dict(eng.stats)


def test_spans_nest_and_carry_their_step(traced):
    reqs, spans, s0, s1 = traced
    names = {s[0] for s in spans}
    assert {"serve.admit", "serve.prefill", "serve.readback",
            "serve.dispatch", "serve.process", "serve.worker_flush",
            "serve.bookkeeping"} <= names
    of = lambda n: [s for s in spans if s[0] == n]
    admits = of("serve.admit")
    for n in ("serve.prefill", "serve.worker_flush"):
        assert all(any(_inside(s, a) for a in admits) for s in of(n)), n
    assert sum(a[3]["rows"] for a in admits) == len(reqs)
    assert all(a[3]["bucket"] in (0, 8, 16, 32, 64) for a in admits)
    # each decode step: dispatched, read back, then processed, in order
    steps = [s[3]["step"] for s in of("serve.dispatch")]
    assert steps == list(range(s0["steps"] + 1, s1["steps"] + 1))
    serve_line = of("serve.dispatch")[0][4]
    reads = {s[3]["step"]: s for s in of("serve.readback")
             if not any(_inside(s, a) for a in admits)}
    procs = {s[3]["step"]: s for s in of("serve.process")}
    assert set(reads) == set(procs) == set(steps)
    for d in of("serve.dispatch"):
        k = d[3]["step"]
        assert 1 <= d[3]["live"] <= 4 and d[3]["bucket"] in (8, 16, 32, 64)
        assert d[1] < reads[k][1] <= reads[k][2] <= procs[k][1]
        assert d[4] == reads[k][4] == procs[k][4] == serve_line
    # the bookkeeping closures run on a thread of their own
    assert {s[4] for s in of("serve.bookkeeping")} - {serve_line}


def test_time_counters_are_sums_of_their_spans(traced):
    _, spans, s0, s1 = traced
    total = lambda n, keep=lambda s: True: sum(
        (s[2] - s[1]) * 1e-9 for s in spans if s[0] == n and keep(s))
    admits = [s for s in spans if s[0] == "serve.admit"]
    in_admit = lambda s: any(_inside(s, a) for a in admits)
    d = lambda k: s1[k] - s0[k]
    assert d("wait_s") == pytest.approx(total("serve.readback"), rel=0.01)
    assert d("prefill_s") == pytest.approx(total("serve.admit"), rel=0.01)
    decode = total("serve.dispatch") \
        + total("serve.readback", lambda s: not in_admit(s))
    assert d("decode_s") == pytest.approx(decode, rel=0.01)
    assert d("wait_s") > 0 and d("host_s") >= 0


def test_profiling_leaves_greedy_tokens_unchanged(lm, traced):
    reqs, _, _, _ = traced
    plain = _engine(lm).generate(_requests())
    assert [r.out_tokens for r in plain] == [r.out_tokens for r in reqs]
    assert [len(r.out_tokens) for r in plain] == MAX_NEWS


def test_gc_spans_name_collections_only_while_installed(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with pl.GcSpans() as hook:
            assert hook in gc.callbacks
            gc.collect()
        assert hook not in gc.callbacks
        gc.collect()
    got = [s for s in _spans(tmp_path) if s[0] == "python.gc"]
    assert len(got) == 1 and got[0][3]["generation"] == 2


def test_programs_carry_the_named_scopes(lm):
    """Every layer of the compressed decode step and the admission's bulk
    compress name their ops (the `op_name` a profile's op events keep)."""
    api, params = lm
    eng = _engine(lm, warm=False)
    tok = jnp.zeros((eng.batch,), jnp.int32)
    cache = eng._cache_init(eng.batch)
    decode = eng._decode_fns[eng.decode_ladder.buckets[-1]]
    hlo = decode.lower(params, tok, cache, tok, tok).compile().as_text()
    assert hlo.startswith("HloModule jit_traced_decode")
    paths = re.findall(r'op_name="([^"]*)"', hlo)
    seen = {p for path in paths for p in path.split("/")}
    assert {"layer_scan", "qkv", "kv_flush", "attend", "mlp", "unembed",
            "sample"} <= seen
    admit = eng._admit_step.lower(params, jnp.zeros((1, 8), jnp.int32),
                                  jnp.full((1,), 8, jnp.int32))
    assert "kv_bulk_compress" in admit.as_text(debug_info=True)
