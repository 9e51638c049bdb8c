"""Transformer building blocks: norms, RoPE, attention (GQA / MLA), MLPs, MoE.

Conventions:
  * activations (B, S, D) bf16 by default; reductions/norms/softmax in f32.
  * weights are plain jnp arrays in nested dicts; layer-stacked weights carry
    a leading L dimension and are consumed by lax.scan (compact HLO — one
    traced body for 96-layer models, essential for 512-device dry-run compiles).
  * attention is chunked (online-softmax over KV blocks) so no (S, S) score
    tensor ever materializes — memory O(S * block) instead of O(S^2).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import attn_hint, logical as shard_hint

Params = dict[str, Any]

DEFAULT_QUERY_BLOCK = 512
DEFAULT_KV_BLOCK = 1024


def _split(key, n):
    return jax.random.split(key, n)


def dense_init(key, d_in, d_out, bias=False, std=None, dtype=jnp.bfloat16):
    std = std if std is not None else 1.0 / np.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * std).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


@jax.custom_vjp
def _matmul_bf16_wgrad(w, x):
    return jnp.einsum("...d,df->...f", x, w, preferred_element_type=jnp.float32)


def _mm_fwd(w, x):
    return _matmul_bf16_wgrad(w, x), (w, x)


def _mm_bwd(res, g):
    w, x = res
    gx = jnp.einsum("...f,df->...d", g, w,
                    preferred_element_type=jnp.float32).astype(x.dtype)
    # weight grad in bf16: the MXU accumulates f32 internally; emitting bf16
    # halves the per-layer cross-DP gradient reduce that fires RIGHT HERE
    # (inside the backward scan) — casts applied any later are downstream of
    # the collective (§Perf, deepseek train multi-pod, two refuted attempts).
    gw = jnp.einsum("...d,...f->df", x, g,
                    preferred_element_type=jnp.bfloat16)
    return gw, gx


_matmul_bf16_wgrad.defvjp(_mm_fwd, _mm_bwd)


def dense(p, x):
    y = _matmul_bf16_wgrad(p["w"], x)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(x.dtype)


def rmsnorm_init(d, dtype=jnp.bfloat16):
    return {"g": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["g"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d, dtype=jnp.bfloat16):
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def layernorm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["g"].astype(jnp.float32) + p["b"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 1e4) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 1e4) -> jax.Array:
    """x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked causal attention (online softmax over KV blocks, GQA-aware).
# ---------------------------------------------------------------------------

def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def chunked_attention(
    q: jax.Array,           # (B, Sq, H, hd)
    k: jax.Array,           # (B, Sk, Hkv, hd)
    v: jax.Array,           # (B, Sk, Hkv, hd_v)
    *,
    causal: bool = True,
    q_offset: jax.Array | int = 0,   # absolute position of q[0] (decode)
    kv_block: int = DEFAULT_KV_BLOCK,
    scale: float | None = None,
) -> jax.Array:
    """Flash-style attention: scan over KV blocks with running (max, sum, acc).

    Never materializes (Sq, Sk) — the working set is (Sq, kv_block), so 32k
    prefill and 512k contexts compile within per-device HBM.
    """
    b, sq, h, hd = q.shape
    _, sk, hkv, _ = k.shape
    hd_v = v.shape[-1]
    n_rep = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    kv_block = min(kv_block, sk)
    nblocks = (sk + kv_block - 1) // kv_block
    pad = nblocks * kv_block - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    k = _repeat_kv(k, n_rep).reshape(b, nblocks, kv_block, h, hd)
    v = _repeat_kv(v, n_rep).reshape(b, nblocks, kv_block, h, hd_v)

    qf = q.astype(jnp.float32) * scale
    q_pos = jnp.arange(sq) + q_offset  # (Sq,)

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, blk = inp
        kv_pos = blk * kv_block + jnp.arange(kv_block)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32))
        mask = kv_pos[None, :] <= q_pos[:, None] if causal else jnp.ones((sq, kv_block), bool)
        valid = kv_pos < sk  # mask the tail padding
        mask = mask & valid[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new == -inf): exp(-inf - -inf) -> use 0
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, hd_v), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, acc0),
        (
            jnp.moveaxis(k, 1, 0),
            jnp.moveaxis(v, 1, 0),
            jnp.arange(nblocks),
        ),
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # (B, Sq, H, hd_v)


def decode_attention(
    q: jax.Array,           # (B, 1, H, hd)
    k: jax.Array,           # (B, S, Hkv, hd)   S-sharded cache friendly
    v: jax.Array,           # (B, S, Hkv, hd_v)
    pos: jax.Array,         # (B,) per-slot positions (scalar broadcasts):
    *,                      # row b attends to <= pos[b]
    scale: float | None = None,
) -> jax.Array:
    """Single-shot decode attention (no KV-chunk scan).

    For one query token the score tensor is only (B, H, S) — there is
    nothing to tile. Crucially this keeps the SEQUENCE dim contraction-
    friendly under GSPMD: with the cache S-sharded (the layout when kv-heads
    don't divide the model axis), softmax stats and the PV contraction
    reduce over S with tiny (B, H) / (B, H, hd) all-reduces instead of the
    involuntary cache replication a chunked dynamic-slice scan causes.
    """
    b, sq, h, hd = q.shape
    _, s, hkv, hd_v = v.shape
    n_rep = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    # cache layout under a serve mesh: slots on data, kv heads on model when
    # divisible (else S on model) — same rule as sharding.kv_cache_spec, so
    # the scatter-updated cache flows in without a reshard
    k = attn_hint(k)
    v = attn_hint(v)
    # bf16-native contractions with f32 accumulation (MXU semantics): casting
    # the cache to f32 would make XLA materialize a full f32 copy of the
    # stacked cache per layer (measured 87 GB/step of pure convert churn on
    # yi-6b decode_32k).
    qg = (q[:, 0] * scale).astype(k.dtype).reshape(b, hkv, n_rep, hd)
    sc = jnp.einsum("bgrd,bsgd->bgrs", qg, k,
                    preferred_element_type=jnp.float32)  # (B, Hkv, rep, S)
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    valid = jnp.arange(s)[None] <= posv[:, None]         # (B, S) per-row horizon
    sc = jnp.where(valid[:, None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bgrs,bsgd->bgrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, hd_v).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------

def gqa_init(key, cfg, dtype=jnp.bfloat16):
    hd = cfg.head_dim
    ks = _split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype=dtype),
    }


def gqa_project_kv(p, x, positions, cfg):
    b, s, _ = x.shape
    hd = cfg.head_dim
    k = dense(p["wk"], x).reshape(b, s, cfg.n_kv_heads, hd)
    v = dense(p["wv"], x).reshape(b, s, cfg.n_kv_heads, hd)
    k = apply_rope(k, positions, cfg.rope_theta)
    return k, v


def gqa_attention(p, x, positions, cfg, *, k=None, v=None, q_offset=0, kv_block=None):
    """Self-attention; pass (k, v) explicitly for decode against a cache.

    TP layout: heads on `model` where divisible (Megatron); the einsums in
    chunked_attention then stay fully local per head-shard and the only
    collective is wo's row-parallel reduce.
    """
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = dense(p["wq"], x).reshape(b, s, cfg.n_heads, hd)
    q = attn_hint(q)
    q = apply_rope(q, positions, cfg.rope_theta)
    if k is None:
        k, v = gqa_project_kv(p, x, positions, cfg)
    out = chunked_attention(
        q, k, v, causal=True, q_offset=q_offset,
        kv_block=kv_block or DEFAULT_KV_BLOCK,
    )
    out = attn_hint(out)
    return dense(p["wo"], out.reshape(b, s, cfg.n_heads * hd))


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, cfg, dtype=jnp.bfloat16):
    ks = _split(key, 8)
    d = cfg.d_model
    h = cfg.n_heads
    r = cfg.kv_lora_rank
    qr = cfg.q_lora_rank
    nope = cfg.qk_nope_head_dim
    rope = cfg.qk_rope_head_dim
    vh = cfg.v_head_dim
    p = {
        # KV path: down-project to the latent, decoupled rope key from x
        "wkv_a": dense_init(ks[0], d, r + rope, dtype=dtype),
        "kv_a_norm": rmsnorm_init(r, dtype),
        "wkv_b": dense_init(ks[1], r, h * (nope + vh), dtype=dtype),
        "wo": dense_init(ks[2], h * vh, d, dtype=dtype),
    }
    if qr:
        p["wq_a"] = dense_init(ks[3], d, qr, dtype=dtype)
        p["q_a_norm"] = rmsnorm_init(qr, dtype)
        p["wq_b"] = dense_init(ks[4], qr, h * (nope + rope), dtype=dtype)
    else:
        p["wq"] = dense_init(ks[5], d, h * (nope + rope), dtype=dtype)
    return p


def mla_latent(p, x, positions, cfg):
    """Compute the cached quantities: latent c_kv (B,S,r) and rope key (B,S,rope)."""
    kv = dense(p["wkv_a"], x)
    c_kv, k_rope = jnp.split(kv, [cfg.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(p["kv_a_norm"], c_kv)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return c_kv, k_rope


def mla_attention(p, x, positions, cfg, *, c_kv=None, k_rope=None, q_offset=0, kv_block=None):
    """MLA: queries against the up-projected latent KV.

    The cache stores only (c_kv, k_rope) — (r + rope) per token instead of
    2*H*hd: the *learned* compression the paper's fixed DCT basis is compared
    against in DESIGN.md §4.
    """
    b, s, d = x.shape
    h = cfg.n_heads
    nope, rope, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if c_kv is None:
        c_kv, k_rope = mla_latent(p, x, positions, cfg)
    if "wq_a" in p:
        q = dense(p["wq_b"], rmsnorm(p["q_a_norm"], dense(p["wq_a"], x)))
    else:
        q = dense(p["wq"], x)
    q = q.reshape(b, s, h, nope + rope)
    q = attn_hint(q)
    q_nope, q_rope = jnp.split(q, [nope], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = dense(p["wkv_b"], c_kv).reshape(b, -1, h, nope + vh)
    kv = shard_hint(kv, "batch", None, "model", None)
    k_nope, v = jnp.split(kv, [nope], axis=-1)
    sk = k_nope.shape[1]
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, sk, h, rope))], axis=-1
    )
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = chunked_attention(
        q_full, k_full, v, causal=True, q_offset=q_offset,
        kv_block=kv_block or DEFAULT_KV_BLOCK,
        scale=1.0 / np.sqrt(nope + rope),
    )
    return dense(p["wo"], out.reshape(b, s, h * vh))


def mla_decode_attention(p, x, positions, cfg, c_kv, k_rope, pos):
    """MLA decode with weight absorption: attention runs in the LATENT space.

    Instead of up-projecting the whole cached latent to per-head K/V every
    step (S x H x (nope+vh) work and memory), fold wkv_b into the query and
    output sides:

        q_lat = q_nope @ Wk_head           (b, 1, h, r)
        score = q_lat . c_kv + q_rope . k_rope
        o_lat = softmax(score) . c_kv      (b, 1, h, r)
        out   = o_lat @ Wv_head

    The S-contractions touch only the rank-r latent (r=512 vs h*(nope+vh) =
    32768 for deepseek-v2) — 64x less decode bandwidth, and S-sharding-
    friendly under GSPMD.
    """
    b, s, d = x.shape
    h = cfg.n_heads
    nope, rope, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    if "wq_a" in p:
        q = dense(p["wq_b"], rmsnorm(p["q_a_norm"], dense(p["wq_a"], x)))
    else:
        q = dense(p["wq"], x)
    q = q.reshape(b, s, h, nope + rope)
    q = attn_hint(q)
    # latent cache layout (sharding.latent_cache_spec): slots on data, S on
    # model — the rank-r contractions below then reduce over the model axis
    # with tiny (B, H) partials instead of gathering the latent store
    c_kv = shard_hint(c_kv, "batch", "model", None)
    k_rope = shard_hint(k_rope, "batch", "model", None)
    q_nope, q_rope = jnp.split(q, [nope], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    wkv_b = p["wkv_b"]["w"].reshape(r, h, nope + vh)
    wk, wv = wkv_b[..., :nope], wkv_b[..., nope:]

    # bf16-native latent contractions, f32 accumulation (see decode_attention)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(wk.dtype), wk,
                       preferred_element_type=jnp.float32)
    sc = jnp.einsum("bqhr,bsr->bhqs", q_lat.astype(c_kv.dtype), c_kv,
                    preferred_element_type=jnp.float32)
    sc = sc + jnp.einsum("bqhp,bsp->bhqs", q_rope.astype(k_rope.dtype), k_rope,
                         preferred_element_type=jnp.float32)
    sc = sc / np.sqrt(nope + rope)
    skv = c_kv.shape[1]
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    valid = jnp.arange(skv)[None] <= posv[:, None]       # (B, S) per-row horizon
    sc = jnp.where(valid[:, None, None], sc, -jnp.inf)
    prob = jax.nn.softmax(sc, axis=-1)
    # contract in score layout, then move heads: XLA:CPU has no bf16 dot
    # kernel that writes the (b, q, h, r) layout directly
    o_lat = jnp.einsum("bhqs,bsr->bhqr", prob.astype(c_kv.dtype), c_kv,
                       preferred_element_type=jnp.float32).transpose(0, 2, 1, 3)
    out = jnp.einsum("bqhr,rhv->bqhv", o_lat.astype(wv.dtype), wv,
                     preferred_element_type=jnp.float32)
    return dense(p["wo"], out.reshape(b, s, h * vh).astype(x.dtype))


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, cfg, d_ff=None, dtype=jnp.bfloat16):
    d_ff = d_ff or cfg.d_ff
    ks = _split(key, 3)
    if cfg.mlp_type == "gated_silu":
        return {
            "wg": dense_init(ks[0], cfg.d_model, d_ff, dtype=dtype),
            "wu": dense_init(ks[1], cfg.d_model, d_ff, dtype=dtype),
            "wd": dense_init(ks[2], d_ff, cfg.d_model, dtype=dtype),
        }
    # squared_relu (nemotron) and gelu (whisper/qwen-style) are 2-matrix MLPs
    return {
        "wu": dense_init(ks[0], cfg.d_model, d_ff, dtype=dtype),
        "wd": dense_init(ks[1], d_ff, cfg.d_model, dtype=dtype),
    }


def mlp(p, x, cfg):
    if cfg.mlp_type == "gated_silu":
        g = shard_hint(dense(p["wg"], x), "batch", None, "model")
        u = shard_hint(dense(p["wu"], x), "batch", None, "model")
        return dense(p["wd"], jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u)
    h = shard_hint(dense(p["wu"], x), "batch", None, "model")
    if cfg.mlp_type == "squared_relu":
        h = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(x.dtype)
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return dense(p["wd"], h)


# ---------------------------------------------------------------------------
# Mixture of Experts (top-k, capacity-based chunked dispatch, EP-shardable)
# ---------------------------------------------------------------------------

def _expert_ffn(xe, wg, wu, wd):
    """Expert matmuls. Batch-dim dots stay bf16->bf16: XLA:CPU has no
    BF16 x BF16 = F32 batch-dot runtime thunk (jit or eager), and on TPU a
    bf16-out dot still accumulates f32 inside the MXU. Elementwise math is
    upcast explicitly. Returns yo (b, e, cap, d) f32."""
    h = jnp.einsum("becd,edf->becf", xe, wg)
    u = jnp.einsum("becd,edf->becf", xe, wu)
    h = (jax.nn.silu(h.astype(jnp.float32)) * u.astype(jnp.float32)).astype(xe.dtype)
    h = shard_hint(h, "batch", "model", None, None)
    return jnp.einsum("becf,efd->becd", h, wd).astype(jnp.float32)

def moe_init(key, cfg, dtype=jnp.bfloat16):
    e = cfg.n_experts
    dm, df = cfg.d_model, cfg.moe_d_ff
    ks = _split(key, 5)
    std = 1.0 / np.sqrt(dm)
    p = {
        "router": dense_init(ks[0], dm, e, dtype=jnp.float32),
        # expert weights stacked on a leading E axis => EP shards axis 0
        "wg": (jax.random.normal(ks[1], (e, dm, df), jnp.float32) * std).astype(dtype),
        "wu": (jax.random.normal(ks[2], (e, dm, df), jnp.float32) * std).astype(dtype),
        "wd": (jax.random.normal(ks[3], (e, df, dm), jnp.float32) / np.sqrt(df)).astype(dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=cfg.moe_d_ff * cfg.n_shared_experts, dtype=dtype)
    return p


def moe_ffn(p, x, cfg, *, group_size: int | None = None,
            capacity_factor: float | None = None, dropless: bool | None = None):
    """Top-k routed experts with per-group capacity dispatch.

    Tokens are processed in groups of `group_size` (lax.scan) so the dispatch
    one-hot is (G, E, C) with C = G*topk/E*cf — bounded VMEM/HBM no matter the
    sequence length.  The einsums keep a clean E axis for expert parallelism:
    GSPMD turns the (tokens->experts) resharding into an all-to-all on the
    'model' mesh axis.

    `dropless=True` sets capacity = group size (no token ever dropped) — used
    by the decode path, where groups are tiny and losing a token corrupts the
    stream.
    """
    group_size = cfg.moe_group_size if group_size is None else group_size
    capacity_factor = cfg.moe_capacity_factor if capacity_factor is None else capacity_factor
    dropless = cfg.moe_dropless if dropless is None else dropless
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    g = min(group_size, s)
    assert s % g == 0, (s, g)
    cap = g if dropless else max(8, int(np.ceil(g * k / e * capacity_factor)))
    cap = min(cap, g)
    # groups are SEQUENCE slices per batch row: the scan axis (s//g) is
    # unsharded while the batch dim stays on DP, so every device advances the
    # group loop in lockstep on its own rows (no cross-device group traffic),
    # and expert weights are re-read only s/g times per layer.
    ngroups = s // g
    groups = jnp.moveaxis(x.reshape(b, ngroups, g, d), 1, 0)    # (nG, b, g, d)

    router_w = p["router"]["w"].astype(jnp.float32)

    def per_group(xg):                                          # (b, g, d)
        logits = jnp.einsum("bgd,de->bge", xg.astype(jnp.float32), router_w)
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, k)                    # (b, g, k)
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)     # renorm over top-k
        onehot = jax.nn.one_hot(topi, e, dtype=jnp.float32)     # (b, g, k, e)
        # per-row position of each (token, slot) within its expert queue
        pos = jnp.cumsum(onehot.reshape(b, g * k, e), axis=1).reshape(b, g, k, e) - 1.0
        keep = (pos < cap) * onehot                             # drop overflow
        # mask carriers in bf16: exact for 0/1 values, halves the HBM cost of
        # the (b, g, k, e, cap) dispatch tensor
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.bfloat16)
        keep16 = keep.astype(jnp.bfloat16)
        disp = jnp.einsum("bgke,bgkec->bgec", keep16, pos_oh)   # (b, g, e, cap)
        comb = jnp.einsum("bgk,bgke,bgkec->bgec",
                          topv.astype(jnp.bfloat16), keep16, pos_oh)
        # EP: the dispatch einsum reshards tokens -> expert-major (all-to-all
        # on `model`); expert matmuls then run local to each expert shard.
        xe = jnp.einsum("bgec,bgd->becd", disp, xg.astype(jnp.bfloat16))
        xe = shard_hint(xe, "batch", "model", None, None)
        yo = _expert_ffn(xe, p["wg"], p["wu"], p["wd"])
        yg = jnp.einsum("bgec,becd->bgd", comb.astype(jnp.float32), yo)
        return yg.astype(x.dtype)

    if ngroups == 1:
        y = per_group(groups[0])[None]
    elif ngroups <= 8:
        # unrolled: the backward then sums the per-group expert-weight grad
        # contributions BEFORE the cross-DP reduction — one all-reduce per
        # layer instead of one per group (§Perf, deepseek train multi-pod)
        y = jnp.stack([per_group(groups[i]) for i in range(ngroups)])
    else:
        y = jax.lax.map(per_group, groups)
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, d)
    if "shared" in p:
        y = y + mlp(p["shared"], x, cfg)
    return y
