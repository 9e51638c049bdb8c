"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes and derive the roofline terms (assignment §MULTI-POD DRY-RUN).

The production meshes need 512 host placeholder devices: `main` adds
--xla_force_host_platform_device_count=512 to XLA_FLAGS before anything
initializes a jax backend (jax locks the device count at first init).
Importing this module (`benchmarks/hlo_breakdown.py` does, for
`build_cell`) leaves XLA_FLAGS alone. Nothing here allocates device memory:
states and inputs are ShapeDtypeStructs, compile is ahead-of-time only.

Usage:
  python -m repro.launch.dryrun                          # full sweep
  python -m repro.launch.dryrun --arch yi_6b --shape train_4k
  python -m repro.launch.dryrun --mesh multi_pod         # only 2x16x16
  python -m repro.launch.dryrun --variant compressed     # paper-technique on

Artifacts: one JSON per cell under benchmarks/artifacts/dryrun/.
"""
import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ARCH_IDS, SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.models import api as model_api
from repro.models.api import SkippedShape
from repro.parallel import sharding as sh
from repro.roofline import analysis as roofline
from repro.serve import engine as serve_engine
from repro.train import step as train_step

ART_DIR = os.path.join(os.path.dirname(__file__), "../../../benchmarks/artifacts/dryrun")


def _microbatches_for(cfg, mesh, global_batch: int = 256) -> int:
    """Grad-accumulation depth: deep enough that a microbatch's activations
    fit HBM, shallow enough that every DP shard still gets >= 1 row (a
    microbatch smaller than the DP width pads half the fleet with zeros —
    measured as useful_flop_ratio 0.12 vs 0.35 on deepseek multi-pod)."""
    from repro.parallel.mesh import dp_size

    # activation footprint scales with ACTIVE params (MoE activations are
    # top-k sized, not total-expert sized)
    active = cfg.param_counts()["active"]
    if active > 2e11:
        n = 16
    elif active > 5e10:
        n = 8
    elif active > 5e9:
        n = 4
    else:
        n = 1
    return max(1, min(n, global_batch // max(dp_size(mesh), 1)))


def _to_shardings(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree)


def build_cell(api, mesh, shape_name: str, variant: str):
    """Returns (fn, example_args, in_shardings) ready for jit().lower()."""
    cfg = api.cfg
    kind = SHAPES[shape_name][2]
    axes = tuple(mesh.axis_names)

    if kind == "train":
        tc = train_step.TrainConfig(
            microbatches=_microbatches_for(cfg, mesh),
            remat="compressed" if variant == "compressed" else "full",
            grad_compress=(variant == "compressed" and "pod" in axes),
        )
        state = jax.eval_shape(lambda: train_step.init_train_state(api, tc))
        sspec = train_step.state_specs(state, mesh, tc)
        batch = api.input_specs(shape_name)
        bspec = train_step.batch_specs(batch, mesh)
        fn = train_step.make_train_step(api, mesh, tc)
        return (
            fn,
            (state, batch),
            (_to_shardings(mesh, sspec), _to_shardings(mesh, bspec)),
            (_to_shardings(mesh, sspec), None),
        )

    params = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    pspec = sh.param_specs(params, mesh, fsdp=True)

    if kind == "prefill":
        batch = api.input_specs(shape_name)
        bspec = train_step.batch_specs(batch, mesh)

        def fwd(p, b):
            return api.forward(p, b, remat="none")

        return (
            fwd,
            (params, batch),
            (_to_shardings(mesh, pspec), _to_shardings(mesh, bspec)),
            None,
        )

    # decode
    specs = api.input_specs(shape_name)
    token, cache, pos = specs["token"], specs["cache"], specs["pos"]
    cspec = sh.cache_specs(cache, cfg, mesh)
    tspec = sh.data_batch_spec(axes, 1, dim0=token.shape[0], mesh=mesh)

    if variant == "compressed" and cfg.attn_type == "gqa" \
            and cfg.vec_pos_decode \
            and cfg.resolved_head_dim % 8 == 0:
        # KVCompress: the int8 DCT store replaces the raw cache
        seq, batch_size, _ = SHAPES[shape_name]
        cache = jax.eval_shape(
            lambda: serve_engine.init_compressed_cache(cfg, batch_size, seq)
        )
        cache_dict = {
            "packed_k": cache.packed_k, "scale_k": cache.scale_k,
            "packed_v": cache.packed_v, "scale_v": cache.scale_v,
            "tail_k": cache.tail_k, "tail_v": cache.tail_v,
        }
        cdspec = sh.cache_specs(cache_dict, cfg, mesh)

        def dec(p, t, c, q):
            import repro.core.kv_cache as kvc
            cc = kvc.CompressedKVCache.from_arrays(
                c["packed_k"], c["scale_k"], c["packed_v"], c["scale_v"],
                c["tail_k"], c["tail_v"], keep=4,
            )
            logits, nc = serve_engine.decode_step_compressed(p, t, cc, q, cfg)
            return logits, {
                "packed_k": nc.packed_k, "scale_k": nc.scale_k,
                "packed_v": nc.packed_v, "scale_v": nc.scale_v,
                "tail_k": nc.tail_k, "tail_v": nc.tail_v,
            }

        return (
            dec,
            (params, token, cache_dict, pos),
            (
                _to_shardings(mesh, pspec),
                NamedSharding(mesh, tspec),
                _to_shardings(mesh, cdspec),
                NamedSharding(mesh, P()),
            ),
            None,
        )

    if variant == "unrolled":
        def dec(p, t, c, q):
            return api.decode_step(p, t, c, q, unroll=True)
    else:
        def dec(p, t, c, q):
            return api.decode_step(p, t, c, q)

    return (
        dec,
        (params, token, cache, pos),
        (
            _to_shardings(mesh, pspec),
            NamedSharding(mesh, tspec),
            _to_shardings(mesh, cspec),
            NamedSharding(mesh, P()),
        ),
        None,
    )


def run_cell(arch_id: str, shape_name: str, multi_pod: bool, variant: str,
             art_dir: str) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell = f"{arch_id}/{shape_name}/{mesh_name}/{variant}"
    cfg = get_config(arch_id)
    ok, why = cfg.shape_supported(shape_name)
    if not ok:
        print(f"[skip] {cell}: {why}")
        rec = {"cell": cell, "status": "skipped", "reason": why}
        os.makedirs(art_dir, exist_ok=True)
        fname = f"{arch_id}__{shape_name}__{mesh_name}__{variant}.json"
        with open(os.path.join(art_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
        return rec
    api = model_api.build(arch_id)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        fn, args, in_sh, out_sh = build_cell(api, mesh, shape_name, variant)
        with jax.set_mesh(mesh):
            jit_kw = {"in_shardings": in_sh}
            if out_sh is not None:
                jit_kw["out_shardings"] = out_sh
            lowered = jax.jit(fn, **jit_kw).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            print(compiled.memory_analysis())
            ca = compiled.cost_analysis()
            print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})
        r = roofline.from_compiled(arch_id, shape_name, mesh_name,
                                   int(np.prod(mesh.devices.shape)), compiled, cfg)
        rec = {
            "cell": cell, "status": "ok", "variant": variant,
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            **r.to_dict(),
        }
        print(f"[ok]   {roofline.format_row(r)}  (compile {t_compile:.0f}s)")
    except SkippedShape as e:
        rec = {"cell": cell, "status": "skipped", "reason": str(e)}
        print(f"[skip] {cell}: {e}")
    except Exception as e:
        rec = {"cell": cell, "status": "error",
               "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
        print(f"[FAIL] {cell}: {type(e).__name__}: {str(e)[:200]}")
    os.makedirs(art_dir, exist_ok=True)
    fname = f"{arch_id}__{shape_name}__{mesh_name}__{variant}.json"
    with open(os.path.join(art_dir, fname), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count=512"


def main():
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {HOST_DEVICES_FLAG}".strip()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="both", choices=["single", "multi_pod", "both"])
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "compressed", "unrolled"])
    ap.add_argument("--art-dir", default=os.path.normpath(ART_DIR))
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi_pod": [True], "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                results.append(run_cell(arch, shape, mp, args.variant, args.art_dir))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_fail = sum(r["status"] == "error" for r in results)
    print(f"\n== dry-run: {n_ok} ok, {n_skip} skipped, {n_fail} FAILED "
          f"of {len(results)} cells ==")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
