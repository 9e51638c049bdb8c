"""The Qwen2 / Llama decoder block: RMSNorm, rotary positions, grouped-query
attention (optional q/k/v biases), SwiGLU MLP, a tied or untied head.

A configuration file names it with `"block": "llama"`; its `config` is the
model's Hugging Face `config.json`, its `arch` the program's registry name
and `qkv_bias` whether the attention has q/k/v biases.  What the harness
reads of a block is listed in `chipbench/spec.py` (`BLOCK_FUNCTIONS`).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as ref


def model_dims(config: dict) -> dict:
    """The published sizes (Hugging Face `config.json` keys) as the
    benchmark's own names."""
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
        "norm_eps": float(c["rms_norm_eps"]),
        "tied": bool(c.get("tie_word_embeddings", False)),
        "qkv_bias": bool(config["qkv_bias"]),
    }


def program(config: dict):
    """The program's model API at the configuration file's sizes."""
    from repro.configs.base import get_config
    from repro.models import api as model_api

    d = model_dims(config)
    base = get_config(config["arch"])
    cfg = dataclasses.replace(
        base, n_layers=d["n_layers"], d_model=d["d_model"],
        n_heads=d["n_heads"], n_kv_heads=d["n_kv_heads"],
        head_dim=d["head_dim"], d_ff=d["d_ff"], vocab_size=d["vocab"],
        rope_theta=d["rope_theta"], qkv_bias=d["qkv_bias"],
        tie_embeddings=d["tied"])
    if (cfg.family, cfg.attn_type, cfg.mlp_type, cfg.norm) != (
            "dense", "gqa", "gated_silu", "rmsnorm"):
        raise ValueError(f"{config['arch']}: the llama block is a dense GQA "
                         "decoder with RMSNorm and a SwiGLU MLP")
    return model_api.build(config["arch"], cfg)


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


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(mm, q, k, v, plen, keep):
    """Grouped-query attention of one sequence, q (S, H, hd), k and v
    (S, Hkv, hd), each query over the KV as the serve path holds it
    (`reference.kv_stored`): with a codec (`keep`), the query at position t
    reads the stored form of every block before its own and the raw K/V
    of its own block, and every key raw while the prompt is read
    (t < plen)."""
    s, h, hd = q.shape
    hkv = k.shape[1]
    rep = h // hkv
    pos = jnp.arange(s)
    t, sk = pos[:, None], pos[None, :]
    causal = sk <= t
    qg = q.reshape(s, hkv, rep, hd) / np.sqrt(hd)
    sc_raw = mm("tgrd,sgd->grts", qg, k)
    if keep is None:
        sc = jnp.where(causal, sc_raw, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return mm("grts,sgd->tgrd", pr, v).reshape(s, h * hd)
    stored = (t >= plen) & (sk // ref.BLOCK < t // ref.BLOCK)   # (S, S)
    kc, vc = ref.kv_stored(k, keep), ref.kv_stored(v, keep)
    sc_st = mm("tgrd,sgd->grts", qg, kc)
    sc = jnp.where(stored, sc_st, sc_raw)
    sc = jnp.where(causal, sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = mm("grts,sgd->tgrd", jnp.where(stored, pr, 0.0), vc) \
        + mm("grts,sgd->tgrd", jnp.where(stored, 0.0, pr), v)
    return o.reshape(s, h * hd)


@functools.partial(jax.jit, static_argnames=("d", "keep", "precision"))
def final_hidden(params, tokens, plen, *, d, keep, precision):
    """Final normed hidden states (S, D) of one sequence, f32.

    `d` is `model_dims` tuple-ised; `plen` the prompt length; `keep` the
    codec's keep, or None for raw K/V.  Layer weights are read one layer at
    a time and upcast to f32.
    """
    d = dict(d)
    mm = ref.matmul(precision)
    s = tokens.shape[0]
    hd, h, hkv = d["head_dim"], d["n_heads"], d["n_kv_heads"]
    f32 = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(s)
    x = f32(params["embed"])[tokens]

    def layer(x, p):
        hn = rms_norm(x, f32(p["ln1"]["g"]), d["norm_eps"])

        def proj(name, width):
            y = mm("sd,df->sf", hn, f32(p["attn"][name]["w"]))
            if "b" in p["attn"][name]:
                y = y + f32(p["attn"][name]["b"])
            return y.reshape(s, width, hd)

        q = rope(proj("wq", h), pos, d["rope_theta"])
        k = rope(proj("wk", hkv), pos, d["rope_theta"])
        o = attention(mm, q, k, proj("wv", hkv), plen, keep)
        x = x + mm("sf,fd->sd", o, f32(p["attn"]["wo"]["w"]))
        hn = rms_norm(x, f32(p["ln2"]["g"]), d["norm_eps"])
        g = mm("sd,df->sf", hn, f32(p["mlp"]["wg"]["w"]))
        u = mm("sd,df->sf", hn, f32(p["mlp"]["wu"]["w"]))
        x = x + mm("sf,fd->sd", jax.nn.silu(g) * u, f32(p["mlp"]["wd"]["w"]))
        return x, None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, params["layers"])
        return rms_norm(x, f32(params["final_norm"]["g"]), d["norm_eps"])


@functools.partial(jax.jit, static_argnames=("d", "precision"))
def logits_rows(params, h, *, d, precision):
    """Logits (rows, V) of final hidden rows h, f32."""
    w = params["embed"].T if dict(d)["tied"] else params["lm_head"]
    with jax.default_matmul_precision("highest"):
        return ref.matmul(precision)("sd,dv->sv", h, w.astype(jnp.float32))
