"""Smoke run of the compressed-KV serve path on a TPU chip.

    python chip_smoke.py              # one chip: the serve main path + checks
    python chip_smoke.py --mesh 4x1   # four chips: mesh engine vs one-chip engine
    python chip_smoke.py --mesh 2x2

The model is qwen2_0_5b at its published widths (24 layers, d_model 896,
14 query / 2 KV heads, head_dim 64, vocab 151936, bf16) with random weights
from --seed.  The engine is built the way `python -m repro.launch.serve
--kv-compress --kv-pool-pages N --aot-warmup` builds it: continuous batching
over the paged DCT-compressed KV pool (uniform dct keep=4), decode attention
in the fused `attend_paged` Pallas kernel, and the whole serving surface
compiled ahead of traffic for small explicit prefill/decode ladders.

One chip: 8 requests (prompts of 64-512 tokens, 32 new tokens each, batch 8,
max_seq 1024) through that engine, then these checks, one line each:

  * the codec resolved to the compiled `pallas` backend, not interpreted;
  * every request got its full token budget, and no compile ran under traffic;
  * on a pool built here, the compiled `attend_paged` agrees with the
    reference `attend_compressed` (f32, highest matmul precision) within
    KERNEL_ATOL;
  * first decode-step logits are finite and agree with a
    `codec_backend="reference"` engine's within LOGITS_RTOL (relative to
    the largest reference logit); greedy agreement is printed;
  * the reference engine and a raw-KV engine (the control) serve the same
    requests to completion.

--mesh runs only the mesh engine and the one-chip engine it is compared
with: greedy tokens must be equal at 4x1; at 2x2 (heads and MLP split
over two chips) first decode-step logits must agree within
MESH_LOGITS_RTOL.

Timings printed here are smoke output, not a benchmark.  Without a TPU the
script exits non-zero and prints no result line; with one, the last line of
standard output is the result: {"ok": true, "device": {...}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "qwen2_0_5b"
PUBLISHED = dict(n_layers=24, d_model=896, n_heads=14, n_kv_heads=2,
                 head_dim=64, vocab_size=151936)
BATCH, MAX_SEQ, MAX_NEW, KEEP = 8, 1024, 32, 4
PROMPT_LENS = (64, 97, 128, 203, 256, 333, 450, 512)
PREFILL_BUCKETS = (128, 512)
DECODE_BUCKETS = (256, 1024)
PAGES = BATCH * MAX_SEQ // 8          # every slot can hold max_seq
# compiled kernel vs the f32 reference on the same pool: both compute in
# f32 at full matmul precision, so only summation order differs
KERNEL_ATOL = 2e-3
# pallas vs reference engine, first decode step: each engine compresses
# the prompt with its own codec backend (an int8 code may flip at a
# rounding boundary) and the reference attend runs its f32 matmuls at the
# TPU's default bf16-pass precision; a wrong page, head or scale moves the
# logits by O(1) of their range, far past this bound
LOGITS_RTOL = 5e-2
# 2x2 mesh vs one chip: the model axis splits the attention-output and
# MLP-down contractions, and the compiled step all-reduces their bf16
# partial sums (two per layer over 24 layers) where one chip rounds each
# f32 sum once; a bf16-level difference of the same kind as the backend
# one above, while a wrong head or page split moves logits by O(1)
MESH_LOGITS_RTOL = 5e-2


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    print(("PASS " if ok else "FAIL ") + msg, flush=True)
    if not ok:
        raise SmokeFailure(msg)


def reject_overrides() -> None:
    """The CPU-test overrides must not turn this into an interpreted or
    reference-backend run."""
    bad = []
    if os.environ.get("REPRO_CODEC_INTERPRET") == "1":
        bad.append("REPRO_CODEC_INTERPRET=1")
    backend = os.environ.get("REPRO_CODEC_BACKEND")
    if backend and backend != "pallas":
        bad.append(f"REPRO_CODEC_BACKEND={backend}")
    if bad:
        sys.exit(f"chip_smoke: {', '.join(bad)} is set; this run must use "
                 "the compiled pallas kernels. Unset it.")


def tpu_device() -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        sys.exit("chip_smoke: JAX found no TPU; nothing to smoke-test")
    return dev


# ---------------------------------------------------------------------------
# Model, requests, engines
# ---------------------------------------------------------------------------

def build_model(seed: int):
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.models import api as model_api

    cfg = get_config(ARCH)
    got = dict(n_layers=cfg.n_layers, d_model=cfg.d_model,
               n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
               head_dim=cfg.resolved_head_dim, vocab_size=cfg.vocab_size)
    api = model_api.build(ARCH, cfg)
    params = api.init(jax.random.PRNGKey(seed))
    dtype = params["embed"].dtype
    n = sum(p.size for p in jax.tree.leaves(params))
    print(f"model: {ARCH} {got} params={n / 1e6:.1f}M dtype={dtype}",
          flush=True)
    check(got == PUBLISHED and dtype == jnp.bfloat16,
          f"{ARCH} at published widths in bf16")
    return api, params


def make_prompts(seed: int, vocab: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in PROMPT_LENS]


def serve_config(mesh=None, **kw):
    from repro.codec import plan as plan_lib
    from repro.serve import engine as E

    base = dict(max_seq=MAX_SEQ, max_new_tokens=MAX_NEW, kv_compress=True,
                plan=plan_lib.as_plan(None, keep=KEEP, codec="dct"),
                pool_pages=PAGES, prefill_buckets=PREFILL_BUCKETS,
                decode_buckets=DECODE_BUCKETS, aot_warmup=True, mesh=mesh)
    base.update(kw)
    return E.ServeConfig(**base)


def make_engine(api, params, sc):
    import jax
    from repro.parallel import sharding as sh
    from repro.serve import engine as E

    if sc.mesh is not None:
        # as launch/serve.py: weights placed per the param rules first
        params = jax.device_put(
            params, sh.param_shardings(params, sc.mesh, fsdp=False))
    t0 = time.perf_counter()
    eng = E.Engine(api, params, sc, batch=BATCH)
    return eng, time.perf_counter() - t0


def serve(name: str, eng, prompts) -> list[list[int]]:
    from repro.serve import engine as E

    before = eng.trace_counts.snapshot()
    done = eng.generate([E.Request(uid=i, prompt=p, max_new=MAX_NEW)
                         for i, p in enumerate(prompts)])
    check(all(len(r.out_tokens) == MAX_NEW for r in done),
          f"{name}: all {len(done)} requests returned {MAX_NEW} tokens")
    if eng.sc.aot_warmup:
        new = eng.trace_counts.delta(before)
        check(not new, f"{name}: no compile under traffic (new traces {new})")
    return [list(r.out_tokens) for r in done]


def agreement(a, b) -> float:
    pairs = [(x, y) for ra, rb in zip(a, b) for x, y in zip(ra, rb)]
    return sum(x == y for x, y in pairs) / max(len(pairs), 1)


def peak_bytes() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


# ---------------------------------------------------------------------------
# First decode step through an engine's own step functions
# ---------------------------------------------------------------------------

def first_step(api, params, sc, prompts):
    """Prefill the prompts into a fresh paged pool (row b owns pages
    b*max_seq/8 ...) and run one decode step, with the step functions
    `Engine` compiles for `sc`.  Returns (prefill logits at each row's last
    prompt token, first decode-step logits, pool, lengths)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import kv_cache as kvc
    from repro.serve import engine as E

    prefill_fn, decode_fn, cache_init, _ = E.make_steps(api, sc)
    nb = MAX_SEQ // kvc.BLOCK
    bucket = max(PREFILL_BUCKETS)
    tokens = np.zeros((BATCH, bucket), np.int32)
    lengths = np.array([len(p) for p in prompts], np.int32)
    page_ids = np.full((BATCH, bucket // kvc.BLOCK), PAGES, np.int32)
    table = np.arange(BATCH * nb, dtype=np.int32).reshape(BATCH, nb)
    for b, p in enumerate(prompts):
        tokens[b, :len(p)] = p
        page_ids[b, :len(p) // kvc.BLOCK] = table[b, :len(p) // kvc.BLOCK]
    flush_page = table[np.arange(BATCH), lengths // kvc.BLOCK]

    def run(params, tokens, lengths, page_ids, table, flush_page):
        logits, rows_update = prefill_fn(params, tokens, lengths)
        rows = jnp.arange(BATCH)
        last = logits[rows, lengths - 1].astype(jnp.float32)
        cache = kvc.paged_write_rows(cache_init(BATCH), rows_update, rows,
                                     page_ids, table)
        token = jnp.argmax(last, axis=-1).astype(jnp.int32)
        step_logits, cache = decode_fn(params, token, cache, lengths,
                                       flush_page)
        return last, step_logits.astype(jnp.float32), cache

    args = [jnp.asarray(a) for a in (tokens, lengths, page_ids, table,
                                     flush_page)]
    if sc.mesh is None:
        out = jax.jit(run)(params, *args)
    else:
        from repro.parallel import sharding as sh

        params = jax.device_put(
            params, sh.param_shardings(params, sc.mesh, fsdp=False))
        with jax.set_mesh(sc.mesh):
            out = jax.jit(run)(params, *args)
    return (*jax.device_get(out[:2]), out[2], args[1])


def rel_diff(a, b) -> float:
    import numpy as np

    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def check_logits(name: str, *arrays) -> None:
    import numpy as np

    check(all(np.isfinite(a).all() for a in arrays),
          f"{name}: prefill and first-step logits are finite")


def check_kernel(cache, lengths, seed: int) -> None:
    """Compiled attend_paged vs the reference scan on layer 0 of `cache`."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.codec import dispatch
    from repro.core import kv_cache as kvc
    from repro.kernels.fused_attend import ops as fa_ops

    check(dispatch.resolve_interpret(None) is False,
          "attend_paged: interpret resolved to False")
    seg = cache.segments[0]
    # the stacked page rows (read at layer 0) beside layer 0's tails
    lc = dict(seg.as_tree(), **{k: seg.planes[k][0] for k in kvc.TAIL_NAMES})
    paged = dict(keep=seg.keep, layer=0, n_pages=cache.n_pages)
    cfg_h, hd = PUBLISHED["n_heads"], PUBLISHED["head_dim"]
    q = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (BATCH, 1, cfg_h, hd), jnp.float32)
    table = cache.block_table
    got = jax.jit(lambda q, lc, pos, t: fa_ops.attend_with_tail(
        q, lc, pos, block_table=t, **paged))(q, lc, lengths, table)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda q, lc, pos, t: kvc.attend_compressed(
            q, lc, pos, paged["keep"], block_table=t, backend="reference",
            layer=0, n_pages=paged["n_pages"]))(q, lc, lengths, table)
    diff = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    check(diff <= KERNEL_ATOL,
          f"compiled attend_paged vs reference attend_compressed on this "
          f"run's pool (layer 0, {int(table.shape[1])} table entries): max "
          f"abs diff {diff:.3e} <= {KERNEL_ATOL}")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def one_chip(api, params, prompts, seed: int) -> None:
    from repro import codec as codec_lib
    from repro.codec import dispatch

    sc = serve_config()
    backend = codec_lib.resolve_backend_name(sc.codec_backend)
    interp = dispatch.resolve_interpret(None)
    print(f"codec: backend={backend} interpret={interp}", flush=True)
    check(backend == "pallas" and interp is False,
          "codec resolved to compiled pallas kernels")

    eng, build_s = make_engine(api, params, sc)
    print(f"engine: paged pool {PAGES} pages, prefill ladder "
          f"{list(eng.ladder.buckets)}, decode ladder "
          f"{list(eng.decode_ladder.buckets)}; build+warmup {build_s:.1f}s "
          f"(warmup_s={eng.stats['warmup_s']:.1f})", flush=True)
    toks = serve("pallas engine", eng, prompts)
    del eng
    print(f"[smoke output] peak_bytes_in_use after pallas engine: "
          f"{peak_bytes()}", flush=True)

    last_p, step_p, cache, lengths = first_step(api, params, sc, prompts)
    check_logits("pallas engine", last_p, step_p)
    check_kernel(cache, lengths, seed)
    del cache

    sc_ref = serve_config(codec_backend="reference", aot_warmup=False)
    last_r, step_r, cache, _ = first_step(api, params, sc_ref, prompts)
    del cache
    check_logits("reference engine", last_r, step_r)
    rel = rel_diff(step_p, step_r)
    greedy = float((step_p.argmax(-1) == step_r.argmax(-1)).mean())
    check(rel <= LOGITS_RTOL,
          f"first decode-step logits, pallas vs reference engine: max abs "
          f"diff / max |ref| = {rel:.3e} <= {LOGITS_RTOL} (greedy argmax "
          f"agreement {greedy:.3f})")
    eng, _ = make_engine(api, params, sc_ref)
    toks_ref = serve("reference engine", eng, prompts)
    del eng
    print(f"greedy token agreement, pallas vs reference engine: "
          f"{agreement(toks, toks_ref):.3f}", flush=True)

    sc_raw = serve_config(kv_compress=False, pool_pages=None,
                          decode_buckets=None, aot_warmup=False)
    eng, _ = make_engine(api, params, sc_raw)
    toks_raw = serve("raw-KV engine (control)", eng, prompts)
    del eng
    print(f"greedy token agreement, compressed (keep={KEEP}) vs raw KV: "
          f"{agreement(toks, toks_raw):.3f}", flush=True)
    print(f"[smoke output] peak_bytes_in_use: {peak_bytes()}", flush=True)


def on_mesh(api, params, prompts, spec: str) -> None:
    import jax
    from repro.parallel import mesh as mesh_lib

    n = len(jax.devices())
    check(n >= 4, f"--mesh {spec} needs 4 chips, have {n}")
    mesh = mesh_lib.make_serve_mesh(spec)
    one_sc, mesh_sc = serve_config(), serve_config(mesh=mesh)

    eng, _ = make_engine(api, params, one_sc)
    toks_one = serve("one-chip engine", eng, prompts)
    del eng
    eng, _ = make_engine(api, params, mesh_sc)
    toks_mesh = serve(f"{spec} mesh engine", eng, prompts)
    del eng

    _, step_one, cache, _ = first_step(api, params, one_sc, prompts)
    del cache
    _, step_mesh, cache, _ = first_step(api, params, mesh_sc, prompts)
    del cache
    check_logits(f"{spec} mesh engine", step_mesh)
    rel = rel_diff(step_mesh, step_one)
    first = float((step_mesh.argmax(-1) == step_one.argmax(-1)).mean())
    agree = agreement(toks_mesh, toks_one)
    print(f"{spec} vs one chip: first-step logits max abs diff / max |ref| "
          f"= {rel:.3e} (argmax agreement {first:.3f}); greedy token "
          f"agreement {agree:.3f}", flush=True)
    if mesh_lib.parse_mesh_spec(spec)[1] == 1:
        check(toks_mesh == toks_one,
              f"{spec}: greedy tokens equal to the one-chip engine's")
    else:
        check(rel <= MESH_LOGITS_RTOL,
              f"{spec}: first-step logits within {MESH_LOGITS_RTOL} of the "
              f"one-chip engine's")
    print(f"[smoke output] peak_bytes_in_use (device 0): {peak_bytes()}",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default=None, choices=("4x1", "2x2"),
                    help="run only the mesh engine and the one-chip engine "
                         "it is compared with (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    reject_overrides()
    device = tpu_device()
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as e:
        sys.exit(f"chip_smoke: the repo's sources are not next to this "
                 f"script ({e})")
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    try:
        api, params = build_model(args.seed)
        prompts = make_prompts(args.seed, PUBLISHED["vocab_size"])
        if args.mesh:
            on_mesh(api, params, prompts, args.mesh)
        else:
            one_chip(api, params, prompts, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"chip_smoke: all checks passed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
