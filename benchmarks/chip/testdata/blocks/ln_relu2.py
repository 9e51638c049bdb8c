"""A decoder block that no configuration of the benchmark uses: grouped-
query attention with rotary positions, LayerNorm (gain and bias) and a
two-matrix squared-ReLU MLP -- the program's `norm="layernorm"`,
`mlp_type="squared_relu"` path.  It lives only under `testdata/`, with its
configuration and cell, to show that a new configuration is new files: it
defines what `chipbench/spec.py` lists in `BLOCK_FUNCTIONS`, reusing the
llama block's rotary positions and attention.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench import reference as ref
from chipbench.blocks.llama import attention, rope
from chipbench.blocks.llama import attn_flops  # noqa: F401 (the same attention)


def model_dims(config: dict) -> dict:
    c = config["config"]
    n_heads = c["num_attention_heads"]
    return {
        "n_layers": c["num_hidden_layers"],
        "d_model": c["hidden_size"],
        "n_heads": n_heads,
        "n_kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim") or c["hidden_size"] // n_heads,
        "d_ff": c["intermediate_size"],
        "vocab": c["vocab_size"],
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["layer_norm_eps"]),
        "tied": bool(c.get("tie_word_embeddings", False)),
    }


def program(config: dict):
    from repro.configs.base import get_config
    from repro.models import api as model_api

    d = model_dims(config)
    cfg = dataclasses.replace(
        get_config(config["arch"]), n_layers=d["n_layers"],
        d_model=d["d_model"], n_heads=d["n_heads"],
        n_kv_heads=d["n_kv_heads"], head_dim=d["head_dim"], d_ff=d["d_ff"],
        vocab_size=d["vocab"], rope_theta=d["rope_theta"], qkv_bias=False,
        tie_embeddings=d["tied"], norm="layernorm", mlp_type="squared_relu")
    if (cfg.family, cfg.attn_type) != ("dense", "gqa"):
        raise ValueError(f"{config['arch']}: the ln_relu2 block is a dense "
                         "GQA decoder")
    return model_api.build(config["arch"], cfg)


def layout(d: dict) -> dict:
    L, D, F, V = d["n_layers"], d["d_model"], d["d_ff"], d["vocab"]
    hq, hkv = d["n_heads"] * d["head_dim"], d["n_kv_heads"] * d["head_dim"]
    leaves = {
        "embed": ((V, D), "embed", None),
        "final_norm/g": ((D,), "gain", None),
        "final_norm/b": ((D,), "bias", None),
        "layers/attn/wq/w": ((L, D, hq), "proj", D),
        "layers/attn/wk/w": ((L, D, hkv), "proj", D),
        "layers/attn/wv/w": ((L, D, hkv), "proj", D),
        "layers/attn/wo/w": ((L, hq, D), "proj", hq),
        "layers/mlp/wu/w": ((L, D, F), "proj", D),
        "layers/mlp/wd/w": ((L, F, D), "proj", F),
    }
    for n in ("ln1", "ln2"):
        leaves[f"layers/{n}/g"] = ((L, D), "gain", None)
        leaves[f"layers/{n}/b"] = ((L, D), "bias", None)
    if not d["tied"]:
        leaves["lm_head"] = ((D, V), "head", None)
    return leaves


def matmul_params(d: dict) -> int:
    D, F, hd = d["d_model"], d["d_ff"], d["head_dim"]
    hq, hkv = d["n_heads"] * hd, d["n_kv_heads"] * hd
    per_layer = D * (hq + 2 * hkv) + hq * D + 2 * D * F
    return d["n_layers"] * per_layer + D * d["vocab"]


def layer_norm(x, p, eps):
    f32 = lambda a: a.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * f32(p["g"]) + f32(p["b"])


@functools.partial(jax.jit, static_argnames=("d", "keep", "precision"))
def final_hidden(params, tokens, plen, *, d, keep, precision):
    d = dict(d)
    mm = ref.matmul(precision)
    s = tokens.shape[0]
    hd, h, hkv = d["head_dim"], d["n_heads"], d["n_kv_heads"]
    f32 = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(s)
    x = f32(params["embed"])[tokens]

    def layer(x, p):
        hn = layer_norm(x, p["ln1"], d["norm_eps"])
        proj = lambda name, width: mm("sd,df->sf", hn, f32(
            p["attn"][name]["w"])).reshape(s, width, hd)
        q = rope(proj("wq", h), pos, d["rope_theta"])
        k = rope(proj("wk", hkv), pos, d["rope_theta"])
        o = attention(mm, q, k, proj("wv", hkv), plen, keep)
        x = x + mm("sf,fd->sd", o, f32(p["attn"]["wo"]["w"]))
        hn = layer_norm(x, p["ln2"], d["norm_eps"])
        u = jnp.square(jax.nn.relu(mm("sd,df->sf", hn,
                                      f32(p["mlp"]["wu"]["w"]))))
        x = x + mm("sf,fd->sd", u, f32(p["mlp"]["wd"]["w"]))
        return x, None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, params["layers"])
        return layer_norm(x, params["final_norm"], d["norm_eps"])


@functools.partial(jax.jit, static_argnames=("d", "precision"))
def logits_rows(params, h, *, d, precision):
    w = params["embed"].T if dict(d)["tied"] else params["lm_head"]
    with jax.default_matmul_precision("highest"):
        return ref.matmul(precision)("sd,dv->sv", h, w.astype(jnp.float32))
