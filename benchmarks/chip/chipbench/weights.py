"""Random bf16 weights from the seed, made on the device in one jitted call.

The tree has the layout the serve engine reads, as the configuration's
block gives it (`layout(d)`: path -> (shape, kind, fan_in), layer leaves
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


def _draw(key, shape, kind, fan_in):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "proj":
        z = z / np.sqrt(fan_in)
    elif kind in ("gain",):
        z = 1.0 + 0.02 * z
    else:  # embed, head, bias
        z = 0.02 * z
    return z.astype(DTYPE)


def _make(key, leaves: dict):
    out = {}
    for path, (shape, kind, fan_in) in leaves.items():
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


def make_params(leaves: dict, seed: int, mesh=None) -> dict:
    """The weight tree of a block's `layout`, made by one jitted call: on
    the default device, or laid out over `mesh` as the serve engine places
    its weights."""
    fn = lambda key: _make(key, leaves)
    shardings = None
    if mesh is not None:
        from repro.parallel import sharding as sh

        shardings = sh.param_shardings(jax.eval_shape(fn, base_key(0)), mesh,
                                       fsdp=False)
    params = jax.jit(fn, out_shardings=shardings)(base_key(seed))
    jax.block_until_ready(params)
    return params


def param_bytes(leaves: dict) -> int:
    return sum(int(np.prod(s)) * 2 for s, _, _ in leaves.values())
