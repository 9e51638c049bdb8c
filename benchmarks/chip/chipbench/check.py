"""The comparison that decides `correct`.

A sample of the requests the window finished, drawn from the seed, runs
through the reference (the configuration's block, `final_hidden` and
`logits_rows`) once, teacher-forced on prompt + served tokens.  It
holds the longest finished request, compared over all its served tokens
(the deepest contexts and decode buckets), and `per_half` requests from
each half of the engine's slots, each compared over its first
`tokens_per_request` served tokens, so that a fault confined to some rows
of the batch shows.  At every compared position the number is the gap by
which the served token's reference logit lies below the reference's best
logit there (0 when the served token is the reference's own greedy
choice).  The run's reading is the widest gap.

With `control=True` the reference is also run in the control precision
over the same sequences, and the control's reading is the widest gap of the
token the control puts first at each position.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference as ref

ROW_CHUNK = 512


def sample(finished: list, seed: int, slot_of: dict, batch: int,
           per_half: int, tokens_per_request: int) -> list:
    """(request, tokens compared) pairs: the longest finished request in
    full, then up to `per_half` others, in seed order, from each half of
    the slots (`slot_of`: uid -> slot the request was served in)."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.out_tokens), -r.uid))
    rest = [r for r in finished if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out = [(longest, len(longest.out_tokens))]
    taken = [0, 0]
    for i in order:
        r = rest[i]
        if r.uid not in slot_of:
            continue
        half = int(slot_of[r.uid] >= batch // 2)
        if taken[half] < per_half:
            taken[half] += 1
            out.append((r, min(len(r.out_tokens), tokens_per_request)))
    return out


def halves(picked: list, slot_of: dict, batch: int) -> tuple[int, int]:
    """How many of the sampled requests were served in each half of the
    slots."""
    n = [0, 0]
    for r, _ in picked:
        if r.uid in slot_of:
            n[int(slot_of[r.uid] >= batch // 2)] += 1
    return n[0], n[1]


def _gaps(block, params, dims, keep, prompt, served, precision):
    """Per served position: (ref best, ref at served, ref at control's
    first choice or None)."""
    import jax.numpy as jnp

    plen, n = len(prompt), len(served)
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    s = len(seq)
    toks = np.zeros(ref.pad_len(s), np.int32)
    toks[:s] = seq
    dkey = tuple(sorted(dims.items()))
    rows = slice(plen - 1, plen - 1 + n)
    h = block.final_hidden(params, jnp.asarray(toks), plen, d=dkey,
                           keep=keep, precision="reference")[rows]
    hc = None
    if precision == "control":
        hc = block.final_hidden(params, jnp.asarray(toks), plen, d=dkey,
                                keep=keep, precision="control")[rows]
    best, at_served, at_ctrl = [], [], []
    served = np.asarray(served)
    for c0 in range(0, n, ROW_CHUNK):
        c1 = min(n, c0 + ROW_CHUNK)
        lg = np.asarray(block.logits_rows(params, h[c0:c1], d=dkey,
                                          precision="reference"))
        best.append(lg.max(-1))
        at_served.append(lg[np.arange(c1 - c0), served[c0:c1]])
        if hc is not None:
            lc = np.asarray(block.logits_rows(params, hc[c0:c1], d=dkey,
                                              precision="control"))
            at_ctrl.append(lg[np.arange(c1 - c0), lc.argmax(-1)])
    best = np.concatenate(best)
    return (best - np.concatenate(at_served),
            best - np.concatenate(at_ctrl) if hc is not None else None)


def readings(block, params, dims: dict, keep: int | None, picked: list,
             control: bool = False) -> dict:
    """Widest gaps over the sampled (request, tokens) pairs (and each
    request's own widest, `request_gaps`; the control's likewise)."""
    per_request, per_ctrl, n_tok, n_off = [], [], 0, 0
    for r, n in picked:
        g, gc = _gaps(block, params, dims, keep, np.asarray(r.prompt),
                      r.out_tokens[:n], "control" if control else "reference")
        per_request.append(float(g.max()))
        n_tok += len(g)
        n_off += int((g > 0).sum())
        if gc is not None:
            per_ctrl.append(float(gc.max()))
    out = {"logit_gap": max(per_request, default=0.0),
           "request_gaps": per_request, "tokens_compared": n_tok,
           "tokens_off_greedy": n_off, "requests_compared": len(picked)}
    if control:
        out["control_logit_gap"] = max(per_ctrl, default=0.0)
        out["control_request_gaps"] = per_ctrl
    return out
