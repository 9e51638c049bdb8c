"""Compile a cell's largest programs for a described v5e, without the chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/chip/rehearse.py \
        --workload <cell> [--rows N]

Compiles the admission step at (rows x top prefill bucket) -- rows defaults
to the cell's batch -- and the decode step (at the top decode bucket where
the cell keeps a page pool), with the compiled pallas kernels, for one chip
of a `v5e:2x2` topology, and prints each program's `memory_analysis()`
beside the weights' size.  One-chip cells only.  What
the chip's compiler refuses here costs no chip time.
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["REPRO_CODEC_BACKEND"] = "pallas"
os.environ["REPRO_CODEC_INTERPRET"] = "0"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run
    from chipbench import spec, weights
    from repro.core import kv_cache as kvc
    from repro.serve import engine as eng

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cell = spec.load_cell(args.workload)
    if cell.chips != 1:
        raise SystemExit(f"{args.workload}: rehearse.py compiles one-chip "
                         "cells only")
    s = cell.serve
    leaves = cell.block.layout(cell.dims)
    api = cell.block.program(cell.config)
    sc = run.serve_config(cell, "pallas")
    prefill_fn, decode_fn, cache_init, _ = eng.make_steps(api, sc)
    admit_fn, step_fn = eng.make_fused_steps(prefill_fn, decode_fn, sc,
                                             paged=sc.paged)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    params = jax.eval_shape(lambda: weights._make(weights.base_key(0), leaves))
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
    batch = s["batch"]
    rows = args.rows or batch
    top = max(s["prefill_buckets"])
    gb = 2 ** 30
    print(f"{args.workload}: weights {weights.param_bytes(leaves) / gb:.3f} GiB")

    def show(name, compiled):
        m = compiled.memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes / gb:.3f} GiB, "
              f"outputs {m.output_size_in_bytes / gb:.3f} GiB, temporaries "
              f"{m.temp_size_in_bytes / gb:.3f} GiB, aliased "
              f"{m.alias_size_in_bytes / gb:.3f} GiB", flush=True)

    admit = jax.jit(admit_fn).lower(
        params, sds((rows, top), jnp.int32), sds((rows,), jnp.int32)).compile()
    show(f"admission {rows}x{top}", admit)
    pool = jax.eval_shape(lambda: cache_init(batch))
    pool = jax.tree.map(lambda a: sds(a.shape, a.dtype), pool)
    vec = sds((batch,), jnp.int32)
    if sc.paged:
        dtop = max(s["decode_buckets"])
        step = jax.jit(functools.partial(step_fn,
                                         attend_blocks=dtop // kvc.BLOCK))
        show(f"decode bucket {dtop}",
             step.lower(params, vec, pool, vec, vec).compile())
    else:
        show("decode", jax.jit(step_fn).lower(params, vec, pool, vec).compile())
    pool_b = sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                 for a in jax.tree.leaves(pool))
    print(f"pool {pool_b / gb:.3f} GiB")


if __name__ == "__main__":
    main()
