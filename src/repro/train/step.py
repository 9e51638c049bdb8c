"""Distributed train step: microbatched grad accumulation, remat/ActCompress,
optional cross-pod GradCompress, AdamW with FSDP/ZeRO-sharded state.

Two gradient-exchange modes:
  * plain (baseline): pure jit + GSPMD — the cross-pod all-reduce is whatever
    XLA schedules (f32 payload).
  * compressed: a partial-manual shard_map over the `pod` axis (data/model
    stay auto/GSPMD). Per-pod local grads are DCT-truncated to int8, exchanged
    with all_gather over `pod`, decompressed and averaged, with per-leaf error
    feedback (core/grad_comp.py). Wire bytes on the slow link drop ~12x.

Microbatching: the (B, S) global batch is reshaped to (n_micro, mb, S) and
scanned; only one microbatch's activations are live at a time, which is what
lets 340B-class configs fit 16 GB HBM (with sequence-sharded, optionally
DCT-compressed, saved residuals).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.codec import plan as plan_lib
from repro.core import grad_comp
from repro.models.api import ModelAPI
from repro.optim import adamw
from repro.parallel import sharding as sh

Params = dict[str, Any]


@dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    remat: str = "full"            # none | full | compressed (ActCompress)
    plan: Any = None               # ActCompress per-layer CompressionPlan
                                   # (plan object | spec string | int keep)
    compress_keep: int = 4         # legacy scalar shim => uniform plan
    codec_backend: Any = None      # legacy backend shim => plan backend
                                   # (None = auto per repro.codec.dispatch)
    codec: Any = None              # codec family override for every layer
                                   # (None = keep the plan's, default dct)
    grad_compress: bool = False    # cross-pod DCT gradient exchange
    grad_compress_keep: int = 5
    grad_reduce_dtype: Any = jnp.bfloat16  # wire dtype of per-microbatch
                                   # grad reduction (accumulation stays f32)
    optimizer: adamw.AdamWConfig = field(default_factory=adamw.AdamWConfig)
    param_dtype: Any = jnp.bfloat16
    fsdp: bool = True


def init_train_state(api: ModelAPI, tc: TrainConfig, seed: int = 0) -> dict[str, Any]:
    params = api.init(jax.random.PRNGKey(seed), dtype=tc.param_dtype)
    state = {
        "params": params,
        "opt": adamw.init_state(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if tc.grad_compress:
        state["gc_residual"] = grad_comp.init_residual(params)
    return state


def state_specs(state: dict[str, Any], mesh: Mesh, tc: TrainConfig):
    """PartitionSpecs for the full train state (opt state mirrors params)."""
    pspec = sh.param_specs(state["params"], mesh, fsdp=tc.fsdp)
    specs = {
        "params": pspec,
        "opt": {
            "m": pspec,
            "v": pspec,
            "count": P(),
        },
        "step": P(),
    }
    if "gc_residual" in state:
        # residuals mirror params except non-compressible leaves, which are
        # scalar placeholders -> P()
        specs["gc_residual"] = jax.tree.map(
            lambda leaf, s: s if np.ndim(leaf) == len(s) else P(),
            state["gc_residual"], pspec,
        )
    return specs


def batch_specs(batch_shapes: dict[str, Any], mesh: Mesh):
    axes = tuple(mesh.axis_names)
    return {
        k: sh.data_batch_spec(axes, v.ndim, dim0=v.shape[0], mesh=mesh)
        for k, v in batch_shapes.items()
    }


def _microbatch(batch: dict, n_micro: int, mesh: Mesh) -> dict:
    """(B, ...) -> (n_micro, B/n_micro, ...) with a DP sharding constraint.

    Uses the trace-time `logical` hint so manual axes (inside the
    GradCompress pod shard_map) are filtered automatically."""

    def reshape(x):
        b = x.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        y = x.reshape(n_micro, b // n_micro, *x.shape[1:])
        return sh.logical(y, None, "batch", *([None] * (y.ndim - 2)))

    return jax.tree.map(reshape, batch)


def make_train_step(api: ModelAPI, mesh: Mesh, tc: TrainConfig):
    """Build the jit-able train step: (state, batch) -> (state, metrics).

    The caller jits it with in/out shardings from state_specs/batch_specs.
    """
    n_micro = tc.microbatches
    # one plan object from config to kernel; the scalar compress_keep /
    # codec_backend fields are uniform-plan shims
    plan = plan_lib.as_plan(tc.plan, keep=tc.compress_keep,
                            backend=tc.codec_backend, codec=tc.codec) \
        if tc.remat == "compressed" else None

    def loss_fn(params, mb):
        kw = {"plan": plan} if plan is not None else {}
        loss, metrics = api.loss(params, mb, remat=tc.remat, **kw)
        return loss, metrics

    def accumulate_grads(params, batch):
        """Scan microbatches; returns (mean grads f32, mean loss).

        Each microbatch's grads are constrained to the PARAM sharding before
        accumulation: the partial-sum -> sharded transition then lowers to a
        reduce-scatter instead of the tuple-all-reduce(+slice) XLA otherwise
        emits per microbatch (measured 2x wire on deepseek-v2 multi-pod,
        EXPERIMENTS.md §Perf).
        """
        micro = _microbatch(batch, n_micro, mesh)
        pspec = sh.param_specs(params, mesh, fsdp=tc.fsdp)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(acc, mb):
            g_acc, loss_acc = acc
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            # bf16 on the wire (halves the per-layer reduce volume), f32 in
            # the accumulator — standard mixed-precision DP practice
            grads = jax.tree.map(lambda g: g.astype(tc.grad_reduce_dtype), grads)
            grads = jax.tree.map(lambda g, s: sh.constrain(g, s), grads, pspec)
            g_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / n_micro, g_acc, grads
            )
            return (g_acc, loss_acc + loss / n_micro), None

        (grads, loss), _ = jax.lax.scan(body, (zero, 0.0), micro)
        return grads, loss

    if tc.grad_compress and "pod" in mesh.axis_names:
        gc_cfg = grad_comp.GradCompressConfig(keep=tc.grad_compress_keep)

        def per_pod(params, residual, batch):
            grads, loss = accumulate_grads(params, batch)
            grads, new_residual = grad_comp.exchange_compressed(
                grads, residual, gc_cfg, axis="pod"
            )
            loss = jax.lax.pmean(loss, "pod")
            return grads, new_residual, loss

        pod_grads = jax.shard_map(
            per_pod,
            mesh=mesh,
            in_specs=(P(), P(), P("pod")),
            out_specs=(P(), P(), P()),
            axis_names={"pod"},
            check_vma=False,
        )

        def step(state, batch):
            grads, new_residual, loss = pod_grads(
                state["params"], state["gc_residual"], batch
            )
            params, opt, om = adamw.apply_updates(
                state["params"], grads, state["opt"], tc.optimizer
            )
            new_state = {
                "params": params,
                "opt": opt,
                "step": state["step"] + 1,
                "gc_residual": new_residual,
            }
            return new_state, {"loss": loss, **om}

        return step

    def step(state, batch):
        grads, loss = accumulate_grads(state["params"], batch)
        params, opt, om = adamw.apply_updates(
            state["params"], grads, state["opt"], tc.optimizer
        )
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        return new_state, {"loss": loss, **om}

    return step


def jit_train_step(api: ModelAPI, mesh: Mesh, tc: TrainConfig, state, batch_like):
    """Convenience: jit with shardings + donated state."""
    step = make_train_step(api, mesh, tc)
    sspec = state_specs(state, mesh, tc)
    bspec = batch_specs(batch_like, mesh)
    to_shard = lambda spec: jax.tree.map(lambda s: NamedSharding(mesh, s), spec)
    return jax.jit(
        step,
        in_shardings=(to_shard(sspec), to_shard(bspec)),
        out_shardings=(to_shard(sspec), None),
        donate_argnums=(0,),
    )
