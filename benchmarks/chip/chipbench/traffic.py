"""The one traffic generator: a workload's `traffic` parameters -> requests.

Offline (closed) traffic: every request is queued before serving starts.
The lengths and their order are fixed by the workload file, the same for
every seed, so a seed never changes the work in the window; a seed draws
the prompts' token ids (and, elsewhere, the weights):

  * one "round" of `round_size` (prompt, output) length pairs is fixed by
    the clipped lognormal's quantiles, prompt and output paired by a
    permutation drawn from the constant `order_seed`;
  * a length's lognormal is given by the `mean` a published trace reports
    and a `sigma`: its median is solved so that the round's clipped,
    rounded lengths have that mean;
  * the queue is `rounds` rounds, each shuffled by `order_seed`, so any
    stretch of the queue carries the same mix;
  * prompt token ids are drawn uniformly from the vocabulary by `--seed`.
"""
from __future__ import annotations

import math
import statistics

import numpy as np


def quantiles_at(median: float, p: dict, n: int) -> np.ndarray:
    """n stratified draws of a lognormal(median, sigma) clipped to
    [min, max]: the quantiles at (i + 0.5) / n, rounded to integers."""
    nd = statistics.NormalDist()
    vals = [median * math.exp(p.get("sigma", 0.0) * nd.inv_cdf((i + 0.5) / n))
            for i in range(n)]
    return np.clip(np.rint(vals), p["min"], p["max"]).astype(np.int64)


def solve_median(p: dict, n: int) -> float:
    """The median whose n clipped quantiles have mean `p["mean"]`."""
    lo, hi = float(p["min"]), float(p["max"])
    if not quantiles_at(lo, p, n).mean() <= p["mean"] <= quantiles_at(hi, p, n).mean():
        raise ValueError(f"mean {p['mean']} is out of reach within "
                         f"[{p['min']}, {p['max']}]")
    for _ in range(60):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if quantiles_at(mid, p, n).mean() < p["mean"] else (lo, mid)
    return hi


def lognormal_quantiles(p: dict, n: int) -> np.ndarray:
    """The round's n lengths of one side (prompt or output)."""
    return quantiles_at(solve_median(p, n), p, n)


def length_round(t: dict) -> list[tuple[int, int]]:
    n = t["round_size"]
    prompts = lognormal_quantiles(t["prompt"], n)
    outputs = lognormal_quantiles(t["output"], n)
    pair = np.random.default_rng(t["order_seed"]).permutation(n)
    return [(int(prompts[i]), int(outputs[pair[i]])) for i in range(n)]


def lengths(t: dict) -> list[tuple[int, int]]:
    """(prompt_len, max_new) per request, in queue order."""
    base = length_round(t)
    rng = np.random.default_rng([t["order_seed"], 1])
    out = []
    for _ in range(t["rounds"]):
        out += [base[i] for i in rng.permutation(len(base))]
    return out


def prompts(t: dict, seed: int, vocab: int) -> list[tuple[np.ndarray, int]]:
    """(prompt token ids int32, max_new) per request, in queue order."""
    lens = lengths(t)
    rng = np.random.default_rng([seed, 1])
    return [(rng.integers(0, vocab, pl, dtype=np.int64).astype(np.int32), mn)
            for pl, mn in lens]
