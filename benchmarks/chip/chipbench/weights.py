"""Random bf16 weights from the seed, made on the device in one jitted call.

The tree has the layout the serve engine reads (a decoder whose layers are
stacked on a leading axis).  The reference reads the same leaves, made anew
from the same seed after the program's state is freed.

Scales: projections N(0, 1/fan_in), embedding (and the untied head's rows)
N(0, 0.02^2) as the usual initialisers give; norm gains 1 + N(0, 0.02^2) and
biases N(0, 0.02^2), so that every parameter of the layer is exercised.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

DTYPE = jnp.bfloat16


def base_key(seed: int) -> jax.Array:
    """A key from a whole number of any size up to 2**62."""
    seed = int(seed)
    assert 0 <= seed < 2 ** 62, seed
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31), seed // 2 ** 31)


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def layout(d: dict) -> dict:
    """path -> (shape, kind, fan_in) of every leaf; layer leaves are stacked."""
    L, D, F, V = d["n_layers"], d["d_model"], d["d_ff"], d["vocab"]
    hq, hkv = d["n_heads"] * d["head_dim"], d["n_kv_heads"] * d["head_dim"]
    leaves = {
        "embed": ((V, D), "embed", None),
        "final_norm/g": ((D,), "gain", None),
        "layers/ln1/g": ((L, D), "gain", None),
        "layers/ln2/g": ((L, D), "gain", None),
        "layers/attn/wq/w": ((L, D, hq), "proj", D),
        "layers/attn/wk/w": ((L, D, hkv), "proj", D),
        "layers/attn/wv/w": ((L, D, hkv), "proj", D),
        "layers/attn/wo/w": ((L, hq, D), "proj", hq),
        "layers/mlp/wg/w": ((L, D, F), "proj", D),
        "layers/mlp/wu/w": ((L, D, F), "proj", D),
        "layers/mlp/wd/w": ((L, F, D), "proj", F),
    }
    if d["qkv_bias"]:
        for n, width in (("wq", hq), ("wk", hkv), ("wv", hkv)):
            leaves[f"layers/attn/{n}/b"] = ((L, width), "bias", None)
    if not d["tied"]:
        leaves["lm_head"] = ((D, V), "head", None)
    return leaves


def _draw(key, shape, kind, fan_in):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "proj":
        z = z / np.sqrt(fan_in)
    elif kind in ("gain",):
        z = 1.0 + 0.02 * z
    else:  # embed, head, bias
        z = 0.02 * z
    return z.astype(DTYPE)


def _make(key, d):
    out = {}
    for path, (shape, kind, fan_in) in layout(d).items():
        k = _leaf_key(key, path)
        if path.startswith("layers/"):
            # one layer at a time, so no f32 copy of a whole stack exists
            ks = jax.random.split(k, shape[0])
            leaf = jax.lax.map(lambda kk: _draw(kk, shape[1:], kind, fan_in), ks)
        else:
            leaf = _draw(k, shape, kind, fan_in)
        node = out
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return out


def make_params(d: dict, seed: int) -> dict:
    """The weight tree on the default device, made by one jitted call."""
    fn = jax.jit(lambda key: _make(key, d))
    params = fn(base_key(seed))
    jax.block_until_ready(params)
    return params


def param_bytes(d: dict) -> int:
    return sum(int(np.prod(s)) * 2 for s, _, _ in layout(d).values())
