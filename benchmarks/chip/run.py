"""Chip benchmark: one run of one cell of `BENCHMARK.json`.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

In one process, on the cell's TPU chips:
  1. exit non-zero unless JAX finds a TPU (and as many chips as the cell
     asks for);
  2. turn on JAX's persistent compile cache at `<checkout>/.jax_cache`;
  3. make the configuration's bf16 weights on the device from --seed, in
     the layout of its block (`chipbench/blocks/`), laid out over the
     cell's `serve.mesh` where it asks for more than one chip;
  4. build the serve `Engine` (the paged dct pool, or raw K/V where
     `serve.kv_compress` is false) with AOT warmup over the cell's own
     prefill and decode ladders -- everything up to here is `setup_s`;
  5. queue the cell's seeded requests and run `Engine.generate` on a
     worker thread;
  6. open the window once `open_after` requests have retired, close it
     after --seconds; with --trace 1 the profiler traces the window's first
     TRACE_SECONDS, over which the per-layer metrics are read;
  7. retire what is still queued or live, free the program's state, and
     compare a seeded sample of the finished requests with the plain
     reference (`chipbench/check.py`).

The last line of standard output is the result, and the numbers compared
are printed beside their limits as the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
SRC = ROOT / "src"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

OPEN_TIMEOUT_S = 300.0
DRAIN_TIMEOUT_S = 120.0
TRACE_SECONDS = 10.0
FAULTS = ("token", "state", "half_batch")


class RunError(Exception):
    """The run could not be made (no chip, no program, a dead server)."""


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------

def device_info(jax, chips: int, platform: str = "tpu") -> dict:
    devs = jax.devices()
    if devs[0].platform != platform:
        raise RunError(f"JAX found no {platform} (platform "
                       f"{devs[0].platform!r}); nothing to measure")
    if len(devs) < chips:
        raise RunError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(jax, chips: int) -> tuple[list, dict]:
    """Peak HBM of each chip: the allocator's peak of buffers in use plus
    its peak of memory reserved for programs' temporaries, which the
    runtime reserves apart from the buffers (a program whose temporaries do
    not fit fails to load); and the fullest chip's allocator stats."""
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    peaks = [int(s.get("peak_bytes_in_use", 0))
             + int(s.get("peak_bytes_reserved", 0)) for s in stats]
    return peaks, stats[max(range(chips), key=peaks.__getitem__)]


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

def serve_config(cell, backend: str | None, mesh=None):
    """The engine's `ServeConfig` as the cell's `serve` section gives it:
    the paged dct pool, or raw bf16 K/V where `kv_compress` is false."""
    from repro.serve.engine import ServeConfig

    s = cell.serve
    kw = dict(max_seq=s["max_seq"], mesh=mesh, aot_warmup=True,
              prefill_buckets=tuple(s["prefill_buckets"]))
    if cell.keep is None:
        return ServeConfig(kv_compress=False, **kw)
    return ServeConfig(kv_compress=True, kv_keep=cell.keep,
                       codec_backend=backend, pool_pages=s["pool_pages"],
                       decode_buckets=tuple(s["decode_buckets"]), **kw)


def build_engine(cell, params, require_compiled: bool, mesh=None):
    from repro.codec import dispatch
    from repro.serve.engine import Engine

    backend = cell.serve.get("codec_backend") if require_compiled else None
    sc = serve_config(cell, backend, mesh)
    if require_compiled and sc.kv_compress:
        name = dispatch.resolve_backend_name(sc.codec_backend)
        if name != "pallas" or dispatch.resolve_interpret():
            raise RunError(f"codec backend {name!r} (interpret="
                           f"{dispatch.resolve_interpret()}): the cell must "
                           "run the compiled pallas kernels")
    return Engine(cell.block.program(cell.config), params, sc,
                  batch=cell.serve["batch"])


def plant_fault(engine, fault: str, vocab: int) -> None:
    """Break the timed decode step underneath the engine (tests only)."""
    import jax
    import jax.numpy as jnp

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")

    def wrap(fn):
        def step(params, token, cache, pos, *rest, **kw):
            if fault == "state":      # the step hands its state back unchanged:
                # a copy taken before the step, which may donate its input
                before = jax.tree.map(jnp.copy, cache)
            tok, pos1, new_cache = fn(params, token, cache, pos, *rest, **kw)
            if fault == "token":      # a token altered where it is produced
                tok = (tok + 1) % vocab
            elif fault == "state":
                new_cache = before
            elif fault == "half_batch":  # half of the rows left out
                rows = jnp.arange(tok.shape[0])
                tok = jnp.where(rows >= tok.shape[0] // 2, token, tok)
            return tok, pos1, new_cache
        return step

    engine._decode = wrap(engine._decode)
    if engine.paged:
        engine._decode_fns = {t: wrap(f)
                              for t, f in engine._decode_fns.items()}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def _serve_thread(engine, requests):
    box = {}

    def target():
        try:
            engine.generate(requests)
        except BaseException as e:  # surfaced on the main thread
            box["error"] = e

    th = threading.Thread(target=target, name="bench-generate", daemon=True)
    th.start()
    return th, box


def _alive(th, box):
    if "error" in box:
        raise RunError(f"the serve loop failed: {box['error']!r}") \
            from box["error"]
    return th.is_alive()


def _sleep_until(t_end: float, th, box, tick) -> None:
    while (left := t_end - time.perf_counter()) > 0:
        if not _alive(th, box):
            raise RunError("the serve loop ended inside the window")
        tick()
        time.sleep(min(0.05, left))


def _trace_context(trace_dir: str, open_ns: int, close_ns: int):
    from chipbench import trace as trace_lib

    path = trace_lib.find_xplane(trace_dir)
    planes = trace_lib.read_planes(path)
    if not planes or not any(p.ops for p in planes):
        raise RunError(f"the trace {path} holds no device operation")
    t0, t1 = trace_lib.window(path, planes, open_ns, close_ns)
    return {"planes": planes, "t0": t0, "t1": t1,
            "window_s": (t1 - t0) * 1e-9}


def _breakdown(tr) -> dict:
    """The ten ops (outer control flow left out) that took most device time
    in the window, summed per kernel or op and averaged over chips, and the
    ten longest idle gaps of the first chip."""
    from chipbench import kernels
    from chipbench import trace as trace_lib

    n = len(tr["planes"])
    ops: dict[str, float] = {}
    for p in tr["planes"]:
        names = {op: kernels.label(op, text) for op, text in p.hlo.items()
                 if trace_lib.opcode(text) not in kernels.CONTAINERS}
        evs = [e for e in p.ops if e[0] in names
               and tr["t0"] <= e[1] < tr["t1"]]
        for k, v in trace_lib.time_by_name(evs, names.get).items():
            ops[k] = ops.get(k, 0.0) + v / n
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps = trace_lib.idle_gaps(tr["planes"][0].ops, tr["t0"], tr["t1"])[:10]
    return {"device_ops": [[k, v * 1e-9] for k, v in top],
            "idle_gaps": [["host (no span)", g * 1e-9] for g in gaps]}


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             end_to_end: list, per_layer: list, t_start: float,
             platform: str = "tpu", fault: str | None = None,
             control: bool = False, log=print) -> tuple[dict, dict]:
    """One run; returns (result line, readings).  `end_to_end` and
    `per_layer` are the cell's metric entries of BENCHMARK.json."""
    import jax

    from chipbench import check, flops, metrics, peaks, traffic, weights
    from chipbench.latency import itl_between, itl_mark, observe_slots
    from repro.parallel.mesh import make_serve_mesh
    from repro.serve.engine import Request

    chips, block = cell.chips, cell.block
    dev = device_info(jax, chips, platform)
    pk = peaks.peaks_for(dev["kind"]) if platform == "tpu" else None
    dims, keep = cell.dims, cell.keep
    mesh = make_serve_mesh(cell.serve["mesh"]) if chips > 1 else None
    leaves = block.layout(dims)
    params = weights.make_params(leaves, seed, mesh)
    engine = build_engine(cell, params, require_compiled=platform == "tpu",
                          mesh=mesh)
    if fault is not None:
        plant_fault(engine, fault, dims["vocab"])
    requests = [Request(uid=i, prompt=p, max_new=m) for i, (p, m) in
                enumerate(traffic.prompts(cell.traffic, seed, dims["vocab"]))]
    setup_s = time.perf_counter() - t_start
    log(f"setup {setup_s:.3f} s; {len(requests)} requests queued")

    slot_of: dict[int, int] = {}    # uid -> the slot that served it
    tick = lambda: observe_slots(engine, slot_of)
    th, box = _serve_thread(engine, requests)
    open_after = cell.workload["open_after"]
    t_wait = time.perf_counter()
    while sum(r.done for r in requests) < open_after:
        tick()
        if not _alive(th, box):
            raise RunError("the serve loop ended before the window opened")
        if time.perf_counter() - t_wait > OPEN_TIMEOUT_S:
            raise RunError(f"{open_after} requests did not retire within "
                           f"{OPEN_TIMEOUT_S} s")
        time.sleep(0.002)

    # a traced run reads the device over the window's first TRACE_SECONDS
    # (the device trace of a longer stretch overflows), and its per-layer
    # metrics over the same stretch; the run itself still lasts `seconds`
    measure_s = min(seconds, TRACE_SECONDS) if trace else seconds
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    traces0 = engine.trace_counts.snapshot()
    stats0 = dict(engine.stats)
    out0 = [len(r.out_tokens) for r in requests]
    itl0 = itl_mark(engine)
    t_open, open_ns = time.perf_counter(), time.time_ns()
    _sleep_until(t_open + measure_s, th, box, tick)
    t_close, close_ns = time.perf_counter(), time.time_ns()
    out1 = [len(r.out_tokens) for r in requests]
    itl1 = itl_mark(engine)
    stats1 = dict(engine.stats)
    if trace:
        jax.profiler.stop_trace()
    _sleep_until(t_open + seconds, th, box, tick)
    new_traces = sum(engine.trace_counts.delta(traces0).values())
    finished = [r for r in requests if r.done]
    queued = sum(1 for r in requests if not r.out_tokens)
    chip_peaks, mem = memory_peak_bytes(jax, chips)
    peak = max(chip_peaks)
    log("memory: " + json.dumps({k: mem.get(k) for k in (
        "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")})
        + f"; peak bytes per chip {chip_peaks}")
    spans = [(len(r.prompt), a, b) for r, a, b in zip(requests, out0, out1)
             if b > a]

    # retire everything: queued requests shrink to one token each, live
    # ones stop at their next step; then the server's state is freed
    for r in requests:
        if not r.done:
            if not r.out_tokens:
                r.prompt = r.prompt[:1]
            r.max_new = 1
    th.join(DRAIN_TIMEOUT_S)
    if _alive(th, box):
        raise RunError(f"the serve loop did not drain in {DRAIN_TIMEOUT_S} s")

    ctx = {
        "window_s": t_close - t_open, "setup_s": setup_s,
        "tokens": sum(out1) - sum(out0),
        "itl_s": itl_between(engine, itl0, itl1),
        "memory_peak_bytes": peak,
        "stats0": stats0, "stats1": stats1,
        "work": flops.window_work(block, dims, keep, spans),
        "block": block, "dims": dims, "keep": keep, "chips": chips,
        "peaks": pk, "trace": None,
    }
    del engine, params
    gc.collect()

    ref_params = weights.make_params(leaves, seed)
    lim = cell.limits
    batch, per_half = cell.serve["batch"], lim["sample_per_half"]
    picked = check.sample(finished, seed, slot_of, batch, per_half,
                          lim["sample_tokens_per_request"])
    low, high = check.halves(picked, slot_of, batch)
    t_ref = time.perf_counter()
    read = check.readings(block, ref_params, dims, keep, picked,
                          control=control)
    read["reference_s"] = time.perf_counter() - t_ref
    del ref_params

    # the control, in the program's place, is compared as the program is
    gap = read["control_logit_gap"] if control else read["logit_gap"]
    gaps = read.pop("control_request_gaps" if control else "request_gaps")
    checks = {
        "logit_gap": {"value": gap, "limit": lim["logit_gap"], "rule": "<="},
        "tokens_compared": {"value": read["tokens_compared"],
                            "limit": lim["sample_tokens_min"], "rule": ">="},
        "requests_low_half": {"value": low, "limit": per_half, "rule": ">="},
        "requests_high_half": {"value": high, "limit": per_half, "rule": ">="},
        "new_traces": {"value": new_traces, "limit": 0, "rule": "<="},
        "queued_at_close": {"value": queued, "limit": 1, "rule": ">="},
    }
    ok = all(c["value"] <= c["limit"] if c["rule"] == "<="
             else c["value"] >= c["limit"] for c in checks.values())

    device = dict(dev, memory_peak_bytes=peak)
    failed = sum(g > lim["logit_gap"] for g in gaps)
    out = {"correct": bool(ok), "attempted": len(spans), "failed": failed,
           "metrics": {}}
    if trace:
        from chipbench import trace as trace_lib

        try:
            ctx["trace"] = tr = _trace_context(trace_dir, open_ns, close_ns)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        busy = [trace_lib.busy_ns(p.ops, tr["t0"], tr["t1"]) * 1e-9
                for p in tr["planes"]]
        device["busy_s"] = sum(busy) / len(busy)
        log(f"device busy s per chip: {busy}")
        device["window_s"] = tr["window_s"]
        where = trace_lib.idle_stretches(tr["planes"][0].ops, tr["t0"],
                                         tr["t1"])
        log("longest idle stretches (s after the window opened + s long): "
            + ", ".join(f"{(a - tr['t0']) * 1e-9:.4f}+{n * 1e-9:.4f}"
                        for a, n in where[:3]))
    for m in (per_layer if trace else end_to_end):
        v = metrics.load_reader(m["name"])(ctx)
        if v is not None:
            out["metrics"][m["name"]] = {"value": float(v), "unit": m["unit"]}
    out["device"] = device
    if trace:
        out["breakdown"] = _breakdown(ctx["trace"])
    out["checks"] = checks
    return out, read


# ---------------------------------------------------------------------------
# Command line
# ---------------------------------------------------------------------------

def cell_metrics(bench: dict, cell_name: str) -> tuple[list, list]:
    """The end-to-end and per-layer entries of BENCHMARK.json for a cell."""
    def mine(m):
        return "workloads" not in m or cell_name in m["workloads"]
    return ([m for m in bench["end_to_end"] if mine(m)],
            [m for m in bench["per_layer"] if mine(m)])


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, control: bool = False, fault: str | None = None) -> int:
    args = parse(argv)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    if not (SRC / "repro").is_dir():
        log(f"run.py: the system under test is not here ({SRC / 'repro'})")
        return 2
    # the compile cache lives inside the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(SRC))
    from chipbench.spec import load_cell, load_json

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = load_cell(args.workload)
    e2e, per_layer = cell_metrics(bench, args.workload)
    try:
        import jax

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        from repro.runtime.compile_cache import enable_compile_cache

        enable_compile_cache()
        out, read = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             end_to_end=e2e, per_layer=per_layer,
                             t_start=T_START, control=control, fault=fault,
                             log=log)
    except RunError as e:
        log(f"run.py: {e}")
        return 3
    log("readings: " + json.dumps(read))
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} {c['rule']} {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
