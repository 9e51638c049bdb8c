"""Operations and bytes the served model needs, from its published sizes.

Counts are of the algorithm, not of what the program happens to run: a
matmul of (m, k) by (k, n) is 2 m k n operations; the block gives the
weights each token multiplies (`matmul_params`) and the operations of one
query's attention (`attn_flops`).  Bytes are those that must cross HBM:
every matmul weight once a step, and the K/V each decode token reads, in
the form the cell keeps it (`keep`: dct pages, or None: raw bf16).
"""
from __future__ import annotations

BLOCK = 8
RAW_ITEMSIZE = 2   # bf16 K/V


def token_flops(block, d: dict, context: int) -> int:
    """One token of the model step: matmuls + attention over its context."""
    return 2 * block.matmul_params(d) + block.attn_flops(d, context)


def weight_bytes(block, d: dict, itemsize: int = 2) -> int:
    """Weights one decode step must read: every matmul weight once."""
    return block.matmul_params(d) * itemsize


def page_bytes(d: dict, keep: int) -> int:
    """One page: an 8-token block of K and V over all layers, each 8x8 tile
    stored as keep*keep int8 codes plus a 4-byte scale."""
    tiles = d["n_kv_heads"] * d["head_dim"] // BLOCK
    return 2 * d["n_layers"] * tiles * (keep * keep + 4)


def raw_position_bytes(d: dict) -> int:
    """One position of raw K and V over all layers."""
    return 2 * d["n_layers"] * d["n_kv_heads"] * d["head_dim"] * RAW_ITEMSIZE


def decode_positions(plen: int, first: int, stop: int):
    """Query positions of served tokens first..stop-1 of one request: token
    0 comes from admission (query at plen-1, no pool read); token j >= 1
    from the decode step at position plen + j - 1."""
    return [plen + j - 1 for j in range(max(first, 1), stop)]


def context_kv_bytes(d: dict, keep: int | None, p: int) -> int:
    """K/V bytes a decode token at position p reads: the pages of its
    flushed context, p // 8 of them, or raw, its p + 1 positions."""
    if keep is None:
        return (p + 1) * raw_position_bytes(d)
    return (p // BLOCK) * page_bytes(d, keep)


def window_work(block, d: dict, keep: int | None, spans) -> dict:
    """Operations and K/V bytes of the tokens served in the window.

    `spans` holds (prompt_len, first, stop) per request: its served tokens
    first..stop-1 fell in the window.  Token 0 comes with the admission of
    the whole prompt (positions 0..plen-1, each over its causal context);
    a decode token at position p attends over p + 1 positions and reads
    `context_kv_bytes`.
    """
    flops = attn = kv = 0
    for plen, first, stop in spans:
        if first == 0 and stop > 0:
            flops += sum(token_flops(block, d, p + 1) for p in range(plen))
        for p in decode_positions(plen, first, stop):
            flops += token_flops(block, d, p + 1)
            attn += block.attn_flops(d, p + 1)
            kv += context_kv_bytes(d, keep, p)
    return {"model_flops": flops, "attn_flops": attn, "kv_bytes": kv}
