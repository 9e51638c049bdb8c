"""End-to-end training driver.

    python -m repro.launch.train --arch qwen2_0_5b --steps 300 \
        --reduced --seq 256 --batch 32 --remat compressed

Runs the full production stack on whatever devices exist: sharded state,
microbatched train step, ActCompress remat, checkpoint/auto-resume,
preemption guard, straggler monitor. `--reduced` scales the architecture to
a CPU-sized model so a few hundred steps run here (examples/ uses it);
omit it on real hardware.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import store
from repro.configs.base import ARCH_IDS, get_config
from repro.data.synthetic import TokenStream
from repro.models import api as model_api
from repro.optim.adamw import AdamWConfig
from repro.parallel import mesh as mesh_lib
from repro.runtime import fault
from repro.runtime.compile_cache import enable_compile_cache
from repro.train import step as train_step


def make_batch_fn(api, seq: int, batch: int):
    cfg = api.cfg
    ts = TokenStream(vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch)

    def batches(step: int):
        b = ts.batch(step)
        out = {"tokens": jnp.asarray(b["tokens"]), "labels": jnp.asarray(b["labels"])}
        if cfg.is_encoder_decoder:
            rng = np.random.default_rng(step)
            out["frames"] = jnp.asarray(
                rng.standard_normal((batch, cfg.encoder_seq_len or 16, cfg.d_model)),
                jnp.bfloat16,
            )
        elif cfg.frontend == "vision_stub":
            rng = np.random.default_rng(step)
            pf = min(cfg.frontend_tokens or 16, 16)
            out["patches"] = jnp.asarray(
                rng.standard_normal((batch, pf, cfg.d_model)), jnp.bfloat16
            )
            out["labels"] = jnp.concatenate(
                [jnp.full((batch, pf), -1, jnp.int32), out["labels"]], axis=1
            )
        return out

    return batches


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_0_5b", choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--remat", default="compressed",
                    choices=["none", "full", "compressed"])
    ap.add_argument("--compress-plan", default=None,
                    help="per-layer CompressionPlan spec for ActCompress, "
                         "e.g. '0-3:keep=6,4-:keep=3' (overrides "
                         "--compress-keep; see repro.codec.plan)")
    ap.add_argument("--compress-keep", "--compress_keep", type=int, default=4,
                    help="legacy uniform keep (shim for --compress-plan)")
    ap.add_argument("--compress-codec", default=None,
                    help="codec family for every layer (dct, bitplane, asc); "
                         "overrides codec= tokens in --compress-plan")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        # widen over the smoke size so the run exercises real matmuls
        cfg = dataclasses.replace(cfg, d_model=256, n_heads=8, head_dim=32,
                                  d_ff=1024, n_layers=min(cfg.n_layers, 8))
    api = model_api.build(args.arch, cfg)

    n_dev = len(jax.devices())
    mp = args.model_par
    mesh = mesh_lib.make_mesh((max(n_dev // mp, 1), mp), ("data", "model"))
    tc = train_step.TrainConfig(
        microbatches=args.microbatches,
        remat=args.remat,
        plan=args.compress_plan,           # None => uniform(compress_keep)
        compress_keep=args.compress_keep,
        codec=args.compress_codec,
        grad_compress=args.grad_compress,
        optimizer=AdamWConfig(lr=args.lr, warmup_steps=20,
                              total_steps=args.steps),
    )

    state = train_step.init_train_state(api, tc)
    n_params = sum(p.size for p in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M devices={n_dev} "
          f"mesh={dict(mesh.shape)} remat={tc.remat}")

    ckpt_root = os.path.join(args.ckpt_dir, cfg.name)
    start = store.latest_step(ckpt_root)
    if start is not None:
        state, start = store.restore(ckpt_root, state)
        print(f"resumed from step {start}")
    else:
        start = 0

    batches = make_batch_fn(api, args.seq, args.batch)
    with jax.set_mesh(mesh):
        step_fn = train_step.jit_train_step(api, mesh, tc, state, batches(0))

        monitor = fault.StragglerMonitor()
        losses = []
        t_prev = time.perf_counter()

        def logged_step(st, b):
            nonlocal t_prev
            st, metrics = step_fn(st, b)
            losses.append(float(metrics["loss"]))
            n = len(losses)
            if n % args.log_every == 0:
                dt = (time.perf_counter() - t_prev) / args.log_every
                t_prev = time.perf_counter()
                print(f"step {start + n:5d} loss {losses[-1]:7.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):7.3f} {dt*1e3:6.0f} ms/step")
            return st, metrics

        state, last, reason = fault.train_loop(
            logged_step, state, batches,
            start_step=start, num_steps=args.steps,
            save_every=args.save_every,
            save_fn=lambda s, st: store.save_async(ckpt_root, s, st),
            monitor=monitor,
        )
    store.wait_pending()
    print(f"exit={reason} at step {last}; first loss {losses[0]:.4f} "
          f"last loss {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
