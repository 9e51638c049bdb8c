"""CompressionPlan sweep: uniform vs pyramid vs budget-solved plans.

For each plan the sweep reports
  * the analytic compressed-KV ratio vs a raw bf16 cache (the paper's
    Table II bandwidth/footprint argument, per plan), and
  * the decode perplexity delta: teacher-forced next-token perplexity of a
    briefly-trained reduced LM decoding step-by-step OUT OF the compressed
    KV pool under the plan, against the same decode over the raw cache.
    (ActCompress leaves the forward bit-identical, so the KV path is where
    a plan's lossiness is visible.)

`--codecs` adds the codec-family dimension: one curve row per registered
family x keep — analytic ratio, MEASURED resident KV bytes of the decoded
cache, and ppl delta — written to the artifact's ``codec_curves`` and fed
straight back into ``CompressionPlan.from_budget(curves=...)``, whose
solved mixed plan is then evaluated against the best uniform row fitting
each budget.

Writes benchmarks/artifacts/plan_sweep.json.  `--smoke` shrinks everything
to the CI-sized configuration (a couple of minutes on CPU).
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.codec import families as families_lib
from repro.codec.plan import CompressionPlan, raw_kv_bytes_per_token
from repro.core import kv_cache as kvc
from repro.data.synthetic import TokenStream
from repro.models import api as model_api
from repro.optim.adamw import AdamWConfig
from repro.parallel import mesh as mesh_lib
from repro.serve import engine as E
from repro.train import step as train_step


def train_params(api, ts, steps: int):
    tc = train_step.TrainConfig(
        microbatches=1, remat="full", param_dtype=jnp.float32,
        optimizer=AdamWConfig(lr=3e-3, warmup_steps=3, total_steps=steps + 20))
    state = train_step.init_train_state(api, tc)
    step = jax.jit(train_step.make_train_step(
        api, mesh_lib.make_mesh((1,), ("data",)), tc), donate_argnums=(0,))
    m = {"loss": jnp.nan}  # steps=0 benchmarks the untrained model
    for i in range(steps):
        b = {k: jnp.asarray(v) for k, v in ts.batch(i).items()}
        state, m = step(state, b)
    return state["params"], float(m["loss"])


def decode_ce(api, params, toks, max_seq: int, sc: E.ServeConfig,
              prefix: int = 8, measure: bool = False):
    """Teacher-forced CE of positions prefix..S-1, decoded one token at a
    time out of the cache `sc` configures (raw or compressed-per-plan).

    With `measure=True` returns ``(ce, measured_kv_bytes)`` — the codec
    families' data-dependent resident bytes of the final cache (what a
    measured-size allocator would actually hold for this traffic)."""
    prefill_fn, decode_fn, _, _ = E.make_steps(api, sc)
    prefill_fn, decode_fn = jax.jit(prefill_fn), jax.jit(decode_fn)
    b, s = toks.shape
    logits, cache = prefill_fn(params, toks[:, :prefix])
    lse = jax.nn.logsumexp(logits[:, -1], axis=-1)
    ce = [lse - jnp.take_along_axis(logits[:, -1], toks[:, prefix:prefix + 1],
                                    axis=-1)[:, 0]]
    for t in range(prefix, s - 1):
        logits, cache = decode_fn(params, toks[:, t], cache, jnp.int32(t))
        lse = jax.nn.logsumexp(logits, axis=-1)
        ce.append(lse - jnp.take_along_axis(logits, toks[:, t + 1:t + 2],
                                            axis=-1)[:, 0])
    out = float(jnp.mean(jnp.stack(ce)))
    if measure:
        measured = kvc.measured_cache_bytes(cache) \
            if hasattr(cache, "segments") else None
        return out, measured
    return out


def _measured_block_bytes_per_token(cfg, measured: float, b: int,
                                    s: int) -> float:
    """Strip the raw bf16 tail rings from an end-of-decode measured total
    and normalize to bytes/token summed over layers — the unit the budget
    solver's curves and fits() reason in (the last partial block of each
    sequence lives in the tails, so only flushed tokens are in planes)."""
    tail = b * cfg.n_layers * 2 * 8 * cfg.n_kv_heads * \
        cfg.resolved_head_dim * 2
    flushed = b * ((s - 1) // 8) * 8
    return (measured - tail) / max(flushed, 1)


def codec_curves(api, params, toks, base_ce, max_seq: int, names, keeps):
    """One measured curve row per (codec family, keep): analytic ratio,
    MEASURED resident KV bytes of the decoded cache, ppl delta — and the
    per-layer measured bytes/token the budget solver consumes."""
    cfg = api.cfg
    raw_bytes = raw_kv_bytes_per_token(cfg) * max_seq
    b, s = toks.shape
    rows = []
    for cname in names:
        for keep in keeps:
            plan = CompressionPlan.uniform(keep).with_codec(cname)
            sc = E.ServeConfig(max_seq=max_seq, kv_compress=True, plan=plan,
                               codec_backend="reference")
            ce, measured = decode_ce(api, params, toks, max_seq, sc,
                                     measure=True)
            per_tok = _measured_block_bytes_per_token(
                cfg, measured, b, s) / cfg.n_layers
            rows.append({
                "codec": cname, "keep": keep,
                "kv_ratio": plan.kv_cache_bytes(cfg, max_seq) / raw_bytes,
                "measured_kv_bytes": measured,
                "bytes_per_token": per_tok,
                "decode_ce": ce,
                "ppl_delta": float(np.exp(ce) - np.exp(base_ce)),
            })
            print(f"codec={cname:9s} keep={keep} "
                  f"kv_ratio={rows[-1]['kv_ratio']:.3f} "
                  f"measured={measured / 1e3:7.1f}kB "
                  f"ppl_delta={rows[-1]['ppl_delta']:+.4f}")
    return rows


def solve_budget_ladder(api, params, toks, base_ce, max_seq: int, curves):
    """Race the curve-solved mixed plan against the best uniform row at a
    ladder of measured-byte budgets.

    At each budget: `from_budget(curves=...)` picks per-layer (codec, keep)
    by measured bytes; the uniform candidates are the curve rows whose
    uniform plan fits the same budget by its own measured accounting.  A
    WIN is the solved mixed plan strictly beating every fitting uniform's
    perplexity while its OWN measured block bytes also stay within the
    budget — better quality at equal-or-smaller measured KV memory."""
    cfg = api.cfg
    # solver budgets are batch=1 over max_seq with a bf16 tail ring (the
    # kv_cache_bytes convention); measured totals normalize through
    # `_measured_block_bytes_per_token` to compare in those terms
    b, s = toks.shape
    tail_bf16 = cfg.n_layers * 2 * 8 * cfg.n_kv_heads * \
        cfg.resolved_head_dim * 2
    dct8 = CompressionPlan.uniform(8).kv_cache_bytes(cfg, max_seq)
    out = []
    for frac in (0.45, 0.6, 0.75, 0.9):
        budget = frac * dct8
        try:
            solved = CompressionPlan.from_budget(cfg, max_seq, budget,
                                                 curves=curves)
        except ValueError:
            continue
        sc = E.ServeConfig(max_seq=max_seq, kv_compress=True, plan=solved,
                           codec_backend="reference")
        ce, measured = decode_ce(api, params, toks, max_seq, sc, measure=True)
        ppl_delta = float(np.exp(ce) - np.exp(base_ce))
        solved_equiv = _measured_block_bytes_per_token(
            cfg, measured, b, s) * max_seq + tail_bf16
        fitting = [r for r in curves
                   if cfg.n_layers * r["bytes_per_token"] * max_seq
                   + tail_bf16 <= budget]
        entry = {"budget_bytes": budget, "budget_frac_of_dct8": frac,
                 "solved_spec": solved.to_spec(),
                 "solved_ppl_delta": ppl_delta,
                 "solved_measured_kv_bytes": measured,
                 "solved_measured_budget_equiv": solved_equiv}
        if fitting:
            best = min(fitting, key=lambda r: (r["ppl_delta"],
                                               r["bytes_per_token"]))
            entry["best_uniform"] = {k: best[k] for k in
                                     ("codec", "keep", "ppl_delta",
                                      "measured_kv_bytes")}
            # WIN: better perplexity than every uniform plan this measured
            # budget admits, with the mixed plan's own measured footprint
            # inside the same budget
            entry["wins"] = bool(ppl_delta < best["ppl_delta"] - 1e-9
                                 and solved_equiv <= budget)
        else:
            entry["best_uniform"] = None
            entry["wins"] = False
        out.append(entry)
        tag = "WIN " if entry["wins"] else "    "
        print(f"{tag}budget={frac:.2f}x dct8  solved={solved.to_spec():48s} "
              f"ppl_delta={ppl_delta:+.4f} measured={measured / 1e3:.1f}kB")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized sweep (reduced arch, few steps)")
    ap.add_argument("--train-steps", type=int, default=30)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--codecs", default=None,
                    help="comma-separated codec families (or 'all') to "
                         "sweep as measured curves; solves mixed plans "
                         "from the curves at a budget ladder and races "
                         "them against the best uniform rows")
    args = ap.parse_args(argv)

    api = model_api.build_reduced(args.arch)
    cfg = api.cfg
    steps = 10 if args.smoke else args.train_steps
    seq = min(args.max_seq, 48 if args.smoke else args.max_seq)
    ts = TokenStream(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)
    params, train_loss = train_params(api, ts, steps)
    toks = jnp.asarray(
        np.stack([ts.batch(1000 + i)["tokens"][0, :seq]
                  for i in range(4)]).astype(np.int32))
    base_ce = decode_ce(api, params, toks, args.max_seq,
                        E.ServeConfig(max_seq=args.max_seq))

    raw_kv = CompressionPlan.uniform(8).kv_cache_bytes(cfg, args.max_seq)
    budget = 0.7 * raw_kv
    plans = {
        "uniform_k8": CompressionPlan.uniform(8),
        "uniform_k4": CompressionPlan.uniform(4),
        "pyramid_8_4": CompressionPlan.pyramid(cfg.n_layers, 8, 4),
        "budget_70pct": CompressionPlan.from_budget(cfg, args.max_seq, budget),
    }

    raw_bytes = raw_kv_bytes_per_token(cfg) * args.max_seq
    results = {"arch": cfg.name, "train_loss": train_loss,
               "base_decode_ce": base_ce,
               "base_ppl": float(np.exp(base_ce)), "plans": {}}
    for name, plan in plans.items():
        sc = E.ServeConfig(max_seq=args.max_seq, kv_compress=True, plan=plan,
                           codec_backend="reference")
        ce = decode_ce(api, params, toks, args.max_seq, sc)
        kv_bytes = plan.kv_cache_bytes(cfg, args.max_seq)
        results["plans"][name] = {
            "spec": plan.to_spec(),
            "keeps": list(plan.keeps(cfg.n_layers)),
            "kv_ratio": kv_bytes / raw_bytes,
            "decode_ce": ce,
            "ppl_delta": float(np.exp(ce) - np.exp(base_ce)),
        }
        print(f"{name:14s} spec={plan.to_spec():40s} "
              f"kv_ratio={kv_bytes / raw_bytes:.3f} "
              f"ppl_delta={results['plans'][name]['ppl_delta']:+.4f}")

    # the budget-solved plan must honor its budget, and the pyramid must be
    # strictly cheaper than the gentlest uniform plan
    assert plans["budget_70pct"].kv_cache_bytes(cfg, args.max_seq) <= budget
    assert results["plans"]["pyramid_8_4"]["kv_ratio"] < \
        results["plans"]["uniform_k8"]["kv_ratio"]

    if args.codecs:
        names = families_lib.available_families() if args.codecs == "all" \
            else [s for s in args.codecs.split(",") if s]
        keeps = (8, 6, 4) if args.smoke else (8, 6, 4, 3, 2)
        curves = codec_curves(api, params, toks, base_ce, args.max_seq,
                              names, keeps)
        results["codec_curves"] = curves
        results["budget_ladder"] = solve_budget_ladder(
            api, params, toks, base_ce, args.max_seq, curves)
        # acceptance: at least one budget where the curve-solved mixed plan
        # beats the best fitting uniform on perplexity at equal-or-smaller
        # measured KV bytes
        assert any(e["wins"] for e in results["budget_ladder"]), \
            results["budget_ladder"]

    art = os.path.join(os.path.dirname(__file__), "artifacts")
    os.makedirs(art, exist_ok=True)
    with open(os.path.join(art, "plan_sweep.json"), "w") as f:
        json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    main()
