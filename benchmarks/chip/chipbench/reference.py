"""The plain reference: the served model and its KV codec in float32 jnp.

What the system computes, written out directly from the published model
(Qwen2 / Llama decoder: RMSNorm, rotary positions, grouped-query
attention, SwiGLU MLP) and from the KV codec's definition, with no
kernels, pages, cache or batching:

  * the K/V of every full 8-token block is stored as its 8x8 tiles
    (8 positions x 8 channels) of orthonormal DCT-II coefficients, the
    top-left keep x keep corner kept, quantised to int8 with one scale per
    tile (max |coefficient| / 127), and read back through the inverse DCT;
  * the query at position t reads the stored (lossy) form of every block
    before its own block, and the raw K/V of its own block; while the
    prompt is being read (t < prompt length) every key is raw.

`precision="control"` computes every matmul on float8_e4m3fn operands
(products summed in float32): the step below the served bfloat16 that the
correctness check must tell apart from a sound run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 8


def dct_matrix() -> np.ndarray:
    """Orthonormal DCT-II: C[u, a] = s(u) cos(pi (2a + 1) u / 16)."""
    u = np.arange(BLOCK)[:, None]
    a = np.arange(BLOCK)[None, :]
    c = np.sqrt(2.0 / BLOCK) * np.cos(np.pi * (2 * a + 1) * u / (2 * BLOCK))
    c[0] /= np.sqrt(2.0)
    return c


def codec_roundtrip(x: jax.Array, keep: int) -> jax.Array:
    """x: (S, H, hd) with S % 8 == 0 -> what the store gives back."""
    s, h, hd = x.shape
    ck = jnp.asarray(dct_matrix()[:keep], jnp.float32)
    tiles = x.reshape(s // BLOCK, BLOCK, h, hd // BLOCK, BLOCK)
    z = jnp.einsum("ua,iahjb,vb->ihjuv", ck, tiles, ck)
    scale = jnp.maximum(jnp.max(jnp.abs(z), axis=(-2, -1), keepdims=True),
                        1e-8) / 127.0
    q = jnp.clip(jnp.round(z / scale), -127, 127)
    back = jnp.einsum("ua,ihjuv,vb->iahjb", ck, q * scale, ck)
    return back.reshape(s, h, hd)


def _mm(precision):
    if precision == "control":
        f8 = jnp.float8_e4m3fn

        def mm(spec, a, b):
            a = a.astype(f8).astype(jnp.float32)
            b = b.astype(f8).astype(jnp.float32)
            return jnp.einsum(spec, a, b)
        return mm
    return lambda spec, a, b: jnp.einsum(spec, a, b)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("d", "keep", "precision"))
def final_hidden(params, tokens, plen, *, d, keep, precision):
    """Final normed hidden states (S, D) of one sequence, f32.

    `d` is the `spec.model_dims` tuple-ised; `plen` the prompt length.
    Layer weights are read one layer at a time and upcast to f32.
    """
    d = dict(d)
    mm = _mm(precision)
    s = tokens.shape[0]
    hd, h, hkv = d["head_dim"], d["n_heads"], d["n_kv_heads"]
    rep = h // hkv
    f32 = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(s)
    t, sk = pos[:, None], pos[None, :]
    causal = sk <= t
    stored = (t >= plen) & (sk // BLOCK < t // BLOCK)      # (S, S)
    x = f32(params["embed"])[tokens]

    def layer(x, p):
        hn = _rms(x, f32(p["ln1"]["g"]), d["norm_eps"])

        def proj(name, width):
            y = mm("sd,df->sf", hn, f32(p["attn"][name]["w"]))
            if "b" in p["attn"][name]:
                y = y + f32(p["attn"][name]["b"])
            return y.reshape(s, width, hd)

        q = _rope(proj("wq", h), pos, d["rope_theta"])
        k = _rope(proj("wk", hkv), pos, d["rope_theta"])
        v = proj("wv", hkv)
        kc, vc = codec_roundtrip(k, keep), codec_roundtrip(v, keep)
        qg = q.reshape(s, hkv, rep, hd) / np.sqrt(hd)
        sc_raw = mm("tgrd,sgd->grts", qg, k)
        sc_st = mm("tgrd,sgd->grts", qg, kc)
        sc = jnp.where(stored, sc_st, sc_raw)
        sc = jnp.where(causal, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = mm("grts,sgd->tgrd", jnp.where(stored, pr, 0.0), vc) \
            + mm("grts,sgd->tgrd", jnp.where(stored, 0.0, pr), v)
        x = x + mm("sf,fd->sd", o.reshape(s, h * hd), f32(p["attn"]["wo"]["w"]))
        hn = _rms(x, f32(p["ln2"]["g"]), d["norm_eps"])
        g = mm("sd,df->sf", hn, f32(p["mlp"]["wg"]["w"]))
        u = mm("sd,df->sf", hn, f32(p["mlp"]["wu"]["w"]))
        x = x + mm("sf,fd->sd", jax.nn.silu(g) * u, f32(p["mlp"]["wd"]["w"]))
        return x, None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, params["layers"])
        return _rms(x, f32(params["final_norm"]["g"]), d["norm_eps"])


@functools.partial(jax.jit, static_argnames=("tied", "precision"))
def logits_rows(params, h, *, tied, precision):
    """Logits (rows, V) of final hidden rows h, f32."""
    w = params["embed"].T if tied else params["lm_head"]
    with jax.default_matmul_precision("highest"):
        return _mm(precision)("sd,dv->sv", h, w.astype(jnp.float32))


def pad_len(n: int, quantum: int = 256) -> int:
    return -(-n // quantum) * quantum
