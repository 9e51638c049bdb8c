"""Operations and bytes the served model needs, from its published sizes.

Counts are of the algorithm, not of what the program happens to run: a
matmul of (m, k) by (k, n) is 2 m k n operations; attention of one query
over a context of c positions is 4 c head_dim operations per query head
(scores and the weighted sum).  Bytes are those that must cross HBM.
"""
from __future__ import annotations

BLOCK = 8


def matmul_params(d: dict) -> int:
    """Weights that each token multiplies: the layers' projections and the
    output head (the embedding lookup is a gather, not a matmul)."""
    D, F, hd = d["d_model"], d["d_ff"], d["head_dim"]
    hq, hkv = d["n_heads"] * hd, d["n_kv_heads"] * hd
    per_layer = D * (hq + 2 * hkv) + hq * D + 3 * D * F
    return d["n_layers"] * per_layer + D * d["vocab"]


def attn_flops(d: dict, context: int) -> int:
    """One query token over `context` positions, all layers."""
    return d["n_layers"] * 4 * context * d["n_heads"] * d["head_dim"]


def token_flops(d: dict, context: int) -> int:
    """One token of the model step: matmuls + attention over its context."""
    return 2 * matmul_params(d) + attn_flops(d, context)


def weight_bytes(d: dict, itemsize: int = 2) -> int:
    """Weights one decode step must read: every matmul weight once."""
    return matmul_params(d) * itemsize


def page_bytes(d: dict, keep: int) -> int:
    """One page: an 8-token block of K and V over all layers, each 8x8 tile
    stored as keep*keep int8 codes plus a 4-byte scale."""
    tiles = d["n_kv_heads"] * d["head_dim"] // BLOCK
    return 2 * d["n_layers"] * tiles * (keep * keep + 4)


def decode_positions(plen: int, first: int, stop: int):
    """Query positions of served tokens first..stop-1 of one request: token
    0 comes from admission (query at plen-1, no pool read); token j >= 1
    from the decode step at position plen + j - 1."""
    return [plen + j - 1 for j in range(max(first, 1), stop)]


def window_work(d: dict, keep: int, spans) -> dict:
    """Operations and pool bytes of the tokens served in the window.

    `spans` holds (prompt_len, first, stop) per request: its served tokens
    first..stop-1 fell in the window.  Token 0 comes with the admission of
    the whole prompt (positions 0..plen-1, each over its causal context);
    a decode token at position p attends over p + 1 positions and reads the
    pages of its flushed context, p // 8 of them.
    """
    pb = page_bytes(d, keep)
    flops = attn = kv = 0
    for plen, first, stop in spans:
        if first == 0 and stop > 0:
            flops += sum(token_flops(d, p + 1) for p in range(plen))
        for p in decode_positions(plen, first, stop):
            flops += token_flops(d, p + 1)
            attn += attn_flops(d, p + 1)
            kv += (p // BLOCK) * pb
    return {"model_flops": flops, "attn_flops": attn, "kv_bytes": kv}
