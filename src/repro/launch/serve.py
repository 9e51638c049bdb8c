"""Serving driver: continuous-batching generation with raw or DCT-compressed
KV cache, optionally sharded over a (data x model) device mesh.

    python -m repro.launch.serve --arch yi_6b --reduced --requests 8 \
        --kv-compress --kv-keep 6

    # 4-way slot-pool sharding (needs 4 devices, e.g. under
    # XLA_FLAGS=--xla_force_host_platform_device_count=4):
    python -m repro.launch.serve --arch yi_6b --reduced --kv-compress \
        --mesh 4x1

The engine is a slot scheduler: requests with different prompt lengths and
budgets stream through a fixed pool of batch slots, each slot at its own
position over the compressed store. `--scheduler static` restores the
lock-step wave baseline. `--mesh DATAxMODEL` places batch slots (and every
compressed-pool plane) on `data` and attention heads on `model`; params are
device_put with the train-path `param_specs` BEFORE the engine builds, so
multi-device serving never silently replicates weights. Reports tokens/s,
slot utilization, and the analytic KV-cache HBM footprint both ways — the
serving analogue of the paper's Table II bandwidth saving.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.codec import plan as plan_lib
from repro.configs.base import ARCH_IDS, get_config
from repro.models import api as model_api
from repro.parallel import mesh as mesh_lib
from repro.parallel import sharding as sh
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve import engine as E


def kv_bytes_per_token(cfg, compressed: bool,
                       plan: plan_lib.CompressionPlan) -> float:
    if not compressed:
        return plan_lib.raw_kv_bytes_per_token(cfg)
    return plan.kv_bytes_per_token(cfg)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--kv-compress", action="store_true")
    ap.add_argument("--kv-keep", type=int, default=4,
                    help="legacy uniform keep (shim for --kv-plan)")
    ap.add_argument("--kv-plan", default=None,
                    help="per-layer CompressionPlan spec, e.g. "
                         "'0-3:keep=6,4-:keep=3' (overrides --kv-keep)")
    ap.add_argument("--kv-budget-mb", type=float, default=None,
                    help="solve the plan from a KV byte budget instead "
                         "(CompressionPlan.from_budget; overrides --kv-plan)")
    ap.add_argument("--kv-codec", default=None,
                    help="codec family for every layer (dct, bitplane, asc); "
                         "overrides any codec= tokens in --kv-plan. Mixed "
                         "families go in the spec: '0-3:keep=6,"
                         "4-:keep=4+codec=bitplane'")
    ap.add_argument("--kv-pool-pages", type=int, default=None,
                    help="paged KV pool: shared page count (one page = one "
                         "8-token block group across all layers); decouples "
                         "slot count from max_seq provisioning")
    ap.add_argument("--kv-page-budget-mb", type=float, default=None,
                    help="paged KV pool sized from a byte budget instead "
                         "(pages = budget // per-plan page bytes)")
    ap.add_argument("--host-pool-pages", type=int, default=None,
                    help="tiered pool: host-RAM page count behind the device "
                         "pool; cold slots' compressed pages spill there "
                         "under page pressure and stream back before the "
                         "slot's next attend")
    ap.add_argument("--host-pool-mb", type=float, default=None,
                    help="size the host tier from a byte budget instead "
                         "(pages = budget // per-plan page bytes)")
    ap.add_argument("--tier-watermarks", default=None,
                    help="LOW,HIGH free-page fractions of the device pool "
                         "(default 0.25,0.5): queued demand with free pages "
                         "under LOW evicts cold slots until HIGH is free")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="copy-on-write prompt-prefix sharing: identical "
                         "prompt prefixes map the same physical pages "
                         "(content-hashed, verified bitwise on device); "
                         "admission reserves only the unshared suffix")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--prefill-buckets", default=None,
                    help="comma-separated prompt-length buckets (multiples "
                         "of 8, <= max-seq) the engine compiles prefill at; "
                         "admission rounds prompts UP to the ladder. Default: "
                         "powers-of-two multiples of 8 capped at max-seq")
    ap.add_argument("--decode-buckets", default=None,
                    help="paged pool only: comma-separated context-length "
                         "buckets (multiples of 8, <= max-seq) the decode "
                         "step is compiled at; each step attends a static "
                         "bucket//8-entry block-table slice covering the "
                         "deepest live slot. 'off' pins the single "
                         "full-capacity step. Default: powers-of-two "
                         "multiples of 8 capped at max-seq")
    ap.add_argument("--decode-tile-pages", type=int, default=8,
                    help="pages the paged attend kernel gathers (and scores "
                         "as one (G*8, head_dim) tile) per grid step; "
                         "shrunk to a divisor of each bucket's block count")
    ap.add_argument("--aot-warmup", action="store_true",
                    help="compile the whole prefill ladder + decode step at "
                         "engine build, so no XLA compile happens under "
                         "traffic (time reported as warmup_s)")
    ap.add_argument("--no-packed-admission", action="store_true",
                    help="admit one prompt per prefill call instead of "
                         "packing all free slots into one bucketed call")
    ap.add_argument("--sync-host", action="store_true",
                    help="disable the one-step-deep async pipeline: read "
                         "each decode step's tokens before dispatching the "
                         "next, and run bookkeeping inline")
    ap.add_argument("--mesh", default=None,
                    help="DATAxMODEL serve mesh, e.g. 4x1 or 2x2 (batch "
                         "slots shard on data, attention heads on model); "
                         "default: single-device")
    ap.add_argument("--scheduler", default="continuous",
                    choices=("continuous", "static"))
    ap.add_argument("--vary-lengths", action="store_true",
                    help="draw prompt lengths/budgets per request (shows the "
                         "slot scheduler retiring and re-admitting)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    api = model_api.build(args.arch, cfg)
    if api.decode_step is None:
        raise SystemExit(f"{args.arch} has no decode path (encoder-decoder cap)")

    params = api.init(jax.random.PRNGKey(0))
    mesh = mesh_lib.make_serve_mesh(args.mesh)
    if mesh is not None:
        # place loaded params per the param rules BEFORE the engine builds:
        # `init` leaves them wherever device 0 is, and feeding that into a
        # multi-device jit would silently replicate (or re-transfer) every
        # call. Serving never FSDP-shards weights (fsdp=False): TP on
        # `model`, replicated across `data`.
        params = jax.device_put(
            params, sh.param_shardings(params, mesh, fsdp=False))
    if args.kv_budget_mb is not None:
        plan = plan_lib.CompressionPlan.from_budget(
            cfg, args.max_seq, args.kv_budget_mb * 1e6, batch=args.batch)
        if args.kv_codec is not None:
            plan = plan.with_codec(args.kv_codec)
    else:
        plan = plan_lib.as_plan(args.kv_plan, keep=args.kv_keep,
                                codec=args.kv_codec)
    buckets = tuple(int(b) for b in args.prefill_buckets.split(",")) \
        if args.prefill_buckets else None
    if args.decode_buckets == "off":
        dec_buckets = False
    elif args.decode_buckets:
        dec_buckets = tuple(int(b) for b in args.decode_buckets.split(","))
    else:
        dec_buckets = None
    sc = E.ServeConfig(
        max_seq=args.max_seq, max_new_tokens=args.max_new,
        kv_compress=args.kv_compress, plan=plan,
        temperature=args.temperature, mesh=mesh,
        pool_pages=args.kv_pool_pages, page_budget_mb=args.kv_page_budget_mb,
        host_pool_pages=args.host_pool_pages, host_pool_mb=args.host_pool_mb,
        tier_watermarks=tuple(float(w) for w in args.tier_watermarks.split(","))
        if args.tier_watermarks else (0.25, 0.5),
        prefix_sharing=args.prefix_sharing,
        prefill_buckets=buckets, aot_warmup=args.aot_warmup,
        packed_admission=not args.no_packed_admission,
        async_host=not args.sync_host,
        decode_buckets=dec_buckets, decode_tile_pages=args.decode_tile_pages,
    )
    eng = E.Engine(api, params, sc, batch=args.batch, scheduler=args.scheduler)

    rng = np.random.default_rng(0)
    requests = []
    for i in range(args.requests):
        plen = args.prompt_len
        max_new = args.max_new
        if args.vary_lengths:
            plen = int(rng.integers(max(1, plen // 4), plen + 1))
            max_new = int(rng.integers(max(1, max_new // 4), max_new + 1))
        requests.append(E.Request(
            uid=i, prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new=max_new))
    done = eng.generate(requests)

    st = eng.stats
    # first token per request is sampled from prefill logits — exclude it
    # from the decode-loop rate
    dec_tok = st["tokens_out"] - st["requests"]
    dec_tps = dec_tok / st["decode_s"] if st["steps"] else 0.0
    print(f"arch={cfg.name} kv_compress={args.kv_compress} "
          f"plan={plan.to_spec()} scheduler={eng.scheduler} "
          f"mesh={mesh_lib.mesh_desc(mesh)}")
    print(f"requests={st['requests']} decode_steps={st['steps']} "
          f"tokens_out={st['tokens_out']} decode_tok/s={dec_tps:.1f} "
          f"slot_util={eng.slot_utilization():.2f}")
    print(f"time split: warmup_s={st['warmup_s']:.2f} "
          f"prefill_s={st['prefill_s']:.2f} decode_s={st['decode_s']:.2f} "
          f"host_s={st['host_s']:.2f}")
    if eng.scheduler == "continuous":
        lat = eng.latency_stats()
        print(f"latency: ttft p50={lat['ttft_p50_s']*1e3:.1f}ms "
              f"p99={lat['ttft_p99_s']*1e3:.1f}ms | "
              f"itl p50={lat['itl_p50_s']*1e3:.1f}ms "
              f"p99={lat['itl_p99_s']*1e3:.1f}ms "
              f"(ladder={list(eng.ladder.buckets)})")
    raw_b = kv_bytes_per_token(cfg, False, plan)
    cmp_b = kv_bytes_per_token(cfg, True, plan)
    print(f"KV bytes/token: raw {raw_b:.0f} vs compressed {cmp_b:.0f} "
          f"({raw_b / cmp_b:.1f}x) -> at {args.max_seq} ctx x batch "
          f"{args.batch}: {raw_b*args.max_seq*args.batch/1e6:.1f} MB vs "
          f"{cmp_b*args.max_seq*args.batch/1e6:.1f} MB")
    if mesh is not None:
        ps = eng.kv_pool_stats()
        print(f"KV pool per device: {ps['kv_bytes_per_device']/1e6:.2f} MB "
              f"of {ps['kv_pool_bytes']/1e6:.2f} MB total "
              f"across {mesh.devices.size} devices")
    if eng.paged:
        ps = eng.kv_pool_stats()
        print(f"paged pool: {ps['pool_pages']} pages x {ps['page_bytes']} B "
              f"(peak in use {ps['peak_pages_in_use']}), "
              f"peak live slots {eng.stats['peak_live_slots']}, "
              f"admissions blocked on pages "
              f"{eng.stats['admit_blocked_on_pages']}, "
              f"{ps['slots_per_gb']:.0f} slots/GB")
        mean_bucket = st["decode_bucket_tokens"] / max(st["steps"], 1)
        print(f"decode ladder {list(eng.decode_ladder.buckets)}: mean bucket "
              f"{mean_bucket:.1f} of {args.max_seq} max-seq tokens/step")
        if sc.tiered:
            print(f"host tier: {ps['host_pool_pages']} pages "
                  f"({ps['host_pool_bytes']/1e6:.2f} MB), "
                  f"spilled {ps['pages_spilled']} / restored "
                  f"{ps['pages_restored']} pages, parked "
                  f"{ps['slots_parked']} / resumed {ps['slots_resumed']} "
                  f"slots, {ps['pages_host_in_use']} host pages in use")
        if sc.prefix_sharing:
            print(f"prefix sharing: {ps['prefix_shared_blocks']} blocks "
                  f"admitted by reference, {ps['shared_physical_pages']} "
                  f"physical pages currently shared, "
                  f"{ps['prefix_demotions']} collision demotions")
    for r in done[:4]:
        print(f"  req {r.uid}: {r.out_tokens[:12]}{'...' if len(r.out_tokens) > 12 else ''}")
    return done


if __name__ == "__main__":
    main()
