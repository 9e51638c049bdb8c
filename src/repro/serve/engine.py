"""Serving engine: continuous batching with raw or DCT-compressed KV.

Layers:
  * `make_steps` — jit-able pure step functions (prefill / decode / cache
    init) plus a `vec_pos` capability flag: transformer families thread a
    PER-SLOT position vector (B,) through decode, so every batch slot runs
    at its own depth.
  * `decode_step_compressed` — the KVCompress decode path: per layer each
    slot's new K/V goes into its own 8-token raw tail; full blocks flush to
    the int8 DCT store; attention streams the compressed store under each
    slot's causal horizon (core/kv_cache.py).
  * `Engine` — continuous-batching request server: admission queue, per-slot
    single-request prefill into a free slot, per-slot retirement on
    EOS/max_new, immediate re-admission. Live slots are never re-prefilled.
    `scheduler="static"` (and families without per-slot positions — the
    recurrent ones, where a scalar step index drives a state, not a cache)
    falls back to wave-at-a-time lock-step batching.

The compressed pool is the serving analogue of the paper's dynamically
allocated feature-map buffer: slots are occupied exactly as long as their
request lives, instead of the whole batch being provisioned for the slowest
request.

PAGED pool (`ServeConfig.pool_pages` / `page_budget_mb`): the dense per-slot
store becomes a shared page pool + per-slot block tables
(`core/kv_cache.py::PagedKVCache`) — the paper's block-granular buffer
allocation taken literally. The engine owns the allocator: a host-side free
list reserves each request's worst-case pages at admission (so a live slot
never stalls mid-flush), gates admission on FREE PAGES instead of free
slots, hands the decode jit a `(B,)` flush-page vector, and re-issues pages
on retirement. Admission splices only the prompt's own blocks through the
block table — nothing max_seq-sized is zero-filled — and greedy tokens stay
bitwise identical to the dense pool while pages are not exhausted.

Mesh-native serving: `ServeConfig.mesh` places the whole serve loop on a
(data x model) device mesh — batch slots (and every `KVSegment` plane of the
compressed pool) shard on `data`, attention heads on `model`, mirroring the
train-path param rules.  `serve_shardings` builds the explicit NamedShardings
and the Engine jits prefill / decode / cache-init / slot write / slot reset
with them, so the decode hot loop is compiled shard-local: each device owns
its slice of the slot pool the way the paper's banks own feature-map buffer
regions, and no step gathers the cache.  mesh=None degenerates to the
single-device behavior, bitwise.

MLA (deepseek-v2) keeps its raw latent cache: the latent IS a learned
compression (kv_lora 512 vs 2*128*128 per token = 64x); stacking a fixed DCT
basis on top of it measurably hurts (DESIGN.md §4) — `compressed=True` falls
back to raw for MLA and logs the fact.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.codec import plan as plan_lib
from repro.core import kv_cache as kvc
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.api import ModelAPI
from repro.parallel import sharding as sh
from repro.serve import pipeline as pl
from repro.serve import tiering

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Compressed-cache decode (GQA families)
# ---------------------------------------------------------------------------

def init_compressed_cache(cfg, batch: int, max_seq: int, keep: int = 4,
                          dtype=jnp.bfloat16, plan=None):
    return kvc.init_compressed_cache(cfg, batch, max_seq, keep=keep,
                                     dtype=dtype, plan=plan)


def _param_runs(cfg, params):
    """Stacked-layer param runs in absolute layer order: (stack, start, stop)."""
    if cfg.family == "moe":
        nk = cfg.first_k_dense
        runs = []
        if nk:
            runs.append((params["dense_layers"], 0, nk))
        runs.append((params["moe_layers"], nk, cfg.n_layers))
        return runs
    return [(params["layers"], 0, cfg.n_layers)]


def decode_step_compressed(
    params: Params,
    token: jax.Array,       # (B,)
    cache,                  # CompressedKVCache | PagedKVCache
    pos: jax.Array,         # (B,) per-slot positions (scalar broadcasts)
    cfg,
    *,
    kv_block: int = 1024,
    codec_backend: str | None = None,
    flush_page: jax.Array | None = None,  # (B,) page ids (paged pool only)
    attend_blocks: int | None = None,     # static table-slice width (paged)
    pages_per_tile: int = 8,              # paged kernel G-page tile width
) -> tuple[jax.Array, Any]:
    """One-token decode against the DCT-compressed KV store.

    Every slot writes its token at its own `pos[b]` (own tail slot, own
    flush) and attends under its own watermark. The kept corner size is per
    layer: the cache's segments carry the materialized CompressionPlan, and
    the layer scan runs once per (segment x param-stack) intersection with
    that segment's static keep and backend. Attention and the block codec
    dispatch through repro.codec: the fused decompress+attend Pallas kernel
    on TPU, the pure-JAX scan elsewhere.

    With a `PagedKVCache`, `flush_page[b]` names the page the engine
    reserved for row b's flush THIS step (out-of-range id = no flush).  The
    block-table row update happens once here — every layer of a slot
    flushes the same block index, so the table is shared — and each layer's
    update/attend scatters/gathers through it.  The layer scan CARRIES each
    segment's stacked page rows with a layer counter (only the per-slot
    tails and the weights are sliced per layer): the flush scatters into
    the carried rows at [layer, page rows] and `attend_paged` reads them
    by (layer, page), so the pool is updated and read in place — no
    per-layer slice or copy of it.  `attend_blocks` (the decode-bucket
    ladder pick, in table entries) statically slices the table the ATTEND
    sees to the occupied context; the flush update and the cache's stored
    table always stay full-width.
    """
    assert cfg.attn_type == "gqa", "compressed cache is for GQA families"
    b_sz = token.shape[0]
    pos = kvc.as_pos_vec(pos, b_sz)
    paged = isinstance(cache, kvc.PagedKVCache)
    n_pages = cache.n_pages if paged else None
    if paged:
        assert flush_page is not None, "paged decode needs the flush_page vector"
        nblocks = cache.block_table.shape[1]
        rows = jnp.arange(b_sz)
        fp = kvc.as_pos_vec(flush_page, b_sz)
        # a row flushes when its tail ring fills AND the engine handed it a
        # page: a speculative step past a slot's reserved horizon (or a
        # retired row) reaches pos%8==7 with the out-of-range id, which
        # must not enter the table the attend kernel DMAs pages through.
        # Non-flushing rows are gated by blk=nblocks here (drop) and by
        # update_layer_paged's own gate on the pool scatter.
        with jax.named_scope("kv_flush"):
            flush_row = (jnp.mod(pos, kvc.BLOCK) == kvc.BLOCK - 1) \
                & (fp >= 0) & (fp < n_pages)
            blk = jnp.where(flush_row, pos // kvc.BLOCK, nblocks)
            block_table = cache.block_table.at[rows, blk].set(fp,
                                                              mode="drop")
            block_table = sh.logical(block_table, "batch", None)
        att_table = kvc.table_view(block_table, attend_blocks)
    else:
        assert flush_page is None, "flush_page is a paged-pool argument"
        fp = None
        block_table = None
        att_table = None
    x = params["embed"][token][:, None, :].astype(params["embed"].dtype)
    positions = pos[:, None]  # (B, 1) per-row rope positions
    norm = T._norm(cfg)
    hd = cfg.resolved_head_dim
    runs = _param_runs(cfg, params)

    def make_layer_step(keep, backend, codec):
        def layer_step(carry, inp):
            # carry: hidden state, the segment's stacked page rows (paged;
            # {} dense) and the layer index within the segment
            h, pool, li = carry
            p, lc = inp["p"], inp["cache"]
            with jax.named_scope("qkv"):
                hn = norm(p["ln1"], h)
                b, s, _ = hn.shape
                q = L.dense(p["attn"]["wq"], hn).reshape(b, s, cfg.n_heads,
                                                         hd)
                q = sh.attn_hint(q)  # heads on `model` (as the cache specs)
                q = L.apply_rope(q, positions, cfg.rope_theta)
                k_new, v_new = L.gqa_project_kv(p["attn"], hn, positions,
                                                cfg)
            with jax.named_scope("kv_flush"):
                if paged:
                    pool, lc2 = kvc.update_layer_paged(
                        pool, li, lc, k_new, v_new, pos, fp, keep,
                        backend=backend, n_pages=n_pages, codec=codec)
                    view = dict(pool, **lc2)
                else:
                    lc2 = view = kvc.update_layer(lc, k_new, v_new, pos, keep,
                                                  backend=backend, codec=codec)
            with jax.named_scope("attend"):
                attn = kvc.attend_auto(q, view, pos, keep, kv_block=kv_block,
                                       backend=backend, block_table=att_table,
                                       pages_per_tile=pages_per_tile,
                                       codec=codec, layer=li,
                                       n_pages=n_pages)
                attn = sh.attn_hint(attn)
                h = h + L.dense(p["attn"]["wo"],
                                attn.reshape(b, s, cfg.n_heads * hd))
            with jax.named_scope("mlp"):
                if "moe" in p:
                    h = h + L.moe_ffn(p["moe"], norm(p["ln2"], h), cfg,
                                      dropless=True)
                else:
                    h = h + L.mlp(p["mlp"], norm(p["ln2"], h), cfg)
            return (h, pool, li + 1), lc2

        return layer_step

    new_segments = []
    for seg in cache.segments:
        layer_step = make_layer_step(
            seg.keep, seg.backend if seg.backend is not None else codec_backend,
            seg.codec)
        seg_tree = seg.as_tree()
        if paged:   # rows ride the carry; only the tails are per layer
            pool = {n: seg_tree[n] for n in seg.row_keys}
            seg_tree = {n: seg_tree[n] for n in kvc.TAIL_NAMES}
        else:
            pool = {}
        parts = []
        for stack, ps, pe in runs:
            s0, s1 = max(seg.start, ps), min(seg.stop, pe)
            if s0 >= s1:
                continue
            pslice = jax.tree.map(lambda p: p[s0 - ps:s1 - ps], stack)
            cslice = jax.tree.map(lambda c: c[s0 - seg.start:s1 - seg.start],
                                  seg_tree)
            # the scope holds the copies XLA places around the loop too
            with jax.named_scope("layer_scan"):
                (x, pool, _), nc = jax.lax.scan(
                    layer_step, (x, pool, jnp.int32(s0 - seg.start)),
                    {"p": pslice, "cache": cslice})
            parts.append(nc)
        new_tree = parts[0] if len(parts) == 1 else \
            jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)
        new_segments.append(seg.replace_arrays(dict(new_tree, **pool)))

    with jax.named_scope("unembed"):
        logits = T.unembed(params, x, cfg)[:, 0]
    if paged:
        return logits, cache.replace(new_segments, block_table)
    return logits, kvc.CompressedKVCache(tuple(new_segments))


def prefill_compressed(
    params: Params,
    tokens: jax.Array,
    cfg,
    max_seq: int,
    keep: int = 4,
    *,
    plan=None,
    lengths: jax.Array | None = None,  # (B,) valid prompt tokens per row
    dtype=jnp.bfloat16,
) -> tuple[jax.Array, kvc.CompressedKVCache]:
    """Prefill into the compressed store: raw prefill then bulk-compress.

    `lengths[b]` is row b's true prompt length (right-padded prompts); it
    drives the per-row tail extraction — full 8-token blocks below the
    row's watermark are DCT-packed, the partial remainder lands raw in the
    row's tail ring. Defaults to the full token-array length for every row
    (the lock-step case).  Each plan segment bulk-compresses its own layer
    range with its own keep (legacy scalar `keep` => uniform plan).

    Only the prompt's own blocks run through the codec; the rest of the
    max_seq store is zero-filled directly, so admission cost scales with
    the prompt, not the pool depth.
    """
    assert cfg.attn_type == "gqa"
    plan = plan_lib.as_plan(plan, keep=keep)
    b, s = tokens.shape
    lengths = kvc.as_pos_vec(s if lengths is None else lengths, b)
    logits, raw = T.prefill(params, tokens, cfg, max_seq, cache_dtype=jnp.float32)
    nb_total = max_seq // kvc.BLOCK
    nb_used = min(-(-s // kvc.BLOCK), nb_total)  # blocks covering the prompt
    segments = []
    for start, stop, pol in plan.segments(cfg.n_layers):
        kseg = pol.kv_keep
        comp = jax.vmap(
            lambda k, v: kvc.prefill_compress(k, v, kseg, pos=lengths,
                                              backend=pol.backend,
                                              codec=pol.codec)
        )(raw["k"][start:stop, :, :nb_used * kvc.BLOCK],
          raw["v"][start:stop, :, :nb_used * kvc.BLOCK])  # vmap over layers
        if nb_used < nb_total:  # zero-fill the unwritten block range (axis 2)
            padb = lambda a: jnp.pad(
                a, ((0, 0), (0, 0), (0, nb_total - nb_used)) + ((0, 0),) * (a.ndim - 3))
            for key in comp:
                if key not in kvc.TAIL_NAMES:
                    comp[key] = padb(comp[key])
        planes = {key: comp[key].astype(dtype) if key in kvc.TAIL_NAMES
                  else comp[key] for key in comp}
        segments.append(kvc.KVSegment(
            planes, keep=kseg, start=start, stop=stop, backend=pol.backend,
            codec=pol.codec,
        ))
    return logits, kvc.CompressedKVCache(tuple(segments))


def prefill_compressed_paged(
    params: Params,
    tokens: jax.Array,      # (1|B, bucket) right-padded prompt, bucket % 8 == 0
    cfg,
    *,
    plan=None,
    keep: int = 4,
    lengths: jax.Array | None = None,
    dtype=jnp.bfloat16,
) -> tuple[jax.Array, tuple]:
    """Prefill one admission bucket into paged slot-update form.

    Unlike the dense path this never materializes (or zero-fills) a
    max_seq-sized store: the raw prefill cache is exactly the bucket, each
    plan segment bulk-compresses only the bucket's blocks, and the result
    is the per-segment update tree `paged_write_slot` scatters into the
    pool at engine-assigned page ids.  Admission cost is O(prompt), not
    O(max_seq) — the paper's "allocate the buffer the layer actually
    needs", applied to admission.
    """
    assert cfg.attn_type == "gqa"
    plan = plan_lib.as_plan(plan, keep=keep)
    b, s = tokens.shape
    assert s % kvc.BLOCK == 0, s
    lengths = kvc.as_pos_vec(s if lengths is None else lengths, b)
    logits, raw = T.prefill(params, tokens, cfg, s, cache_dtype=jnp.float32)
    update = []
    for start, stop, pol in plan.segments(cfg.n_layers):
        kseg = pol.kv_keep
        with jax.named_scope("kv_bulk_compress"):
            comp = jax.vmap(
                lambda k, v: kvc.prefill_compress(k, v, kseg, pos=lengths,
                                                  backend=pol.backend,
                                                  codec=pol.codec)
            )(raw["k"][start:stop], raw["v"][start:stop])  # over layers
        comp["tail_k"] = comp["tail_k"].astype(dtype)
        comp["tail_v"] = comp["tail_v"].astype(dtype)
        update.append(comp)
    return logits, tuple(update)


# ---------------------------------------------------------------------------
# Step factories
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ServeConfig:
    max_seq: int = 2048
    max_new_tokens: int = 64
    kv_compress: bool = False
    kv_keep: int = 4             # legacy scalar shim => CompressionPlan.uniform
    plan: Any = None             # CompressionPlan | spec string | int keep
    temperature: float = 0.0     # 0 => greedy
    eos_id: int = -1             # -1 => never stops early
    kv_block: int = 1024
    codec_backend: str | None = None  # None = auto (repro.codec.dispatch)
    mesh: Any = None             # jax.sharding.Mesh: shard the serve loop on
                                 # (data, model); None = single-device path
    # Paged pool (the paper's dynamic feature-map buffer allocation): set
    # either knob to replace the dense per-slot store with a shared page
    # pool + block tables. `pool_pages` sizes the pool directly in 8-token
    # block groups; `page_budget_mb` solves the page count from a byte
    # budget using the plan's per-layer accounting (pool_pages wins when
    # both are set). Requires kv_compress on a GQA family with the
    # continuous scheduler.
    pool_pages: int | None = None
    page_budget_mb: float | None = None
    # Serving pipeline (continuous scheduler only). `prefill_buckets` fixes
    # the AOT prompt-length ladder admission rounds up to (None = automatic
    # powers-of-two multiples of the 8-token block capped at max_seq); a
    # prompt that fits no bucket raises instead of silently compiling under
    # traffic. `aot_warmup` compiles the whole serving surface (every
    # rows x bucket admission shape, the fused decode step, slot splice /
    # reset / fix) at Engine construction; the cost lands in
    # stats["warmup_s"], never in prefill/decode time. `packed_admission`
    # admits all currently-free slots in ONE bucketed prefill call;
    # `async_host` runs the decode loop one step deep (dispatch t+1 before
    # reading t's tokens) with bookkeeping on a background thread. Both
    # default on; turning them off restores the serial/synchronous loop the
    # parity tests pin against.
    prefill_buckets: Any = None
    aot_warmup: bool = False
    packed_admission: bool = True
    async_host: bool = True
    # Decode-bucket ladder (paged pool only). Each bucket owns a jitted
    # decode step whose attend covers a static `bucket // 8`-entry slice of
    # the block table; the engine picks the smallest bucket covering the
    # deepest live slot's flushed context each step, so decode cost tracks
    # OCCUPIED context instead of pool capacity. None = automatic
    # powers-of-two ladder (pipeline.auto_buckets); False/"off" = single
    # full-capacity bucket (the pre-ladder behaviour); an explicit tuple
    # narrows it. `decode_tile_pages` is the paged kernel's G: pages
    # gathered (and decompressed/scored as one (G*8, hd) tile) per grid
    # step — 8 fills the MXU's 128-lane contraction at hd>=...; shrunk to a
    # divisor of the bucket's block count per jit.
    decode_buckets: Any = None
    decode_tile_pages: int = 8
    # Tiered page pool (requires the paged pool): either knob sizes a host
    # RAM backing store (serve/tiering.py::TierManager) that cold pages
    # spill to when the device free list runs low — the paper's off-chip
    # DRAM tier behind the on-chip buffer, with compressed pages keeping
    # the transfers cheap. `tier_watermarks=(low, high)` are free-page
    # FRACTIONS of the device pool: queued demand with free pages below
    # `low` parks cold slots (latest-admitted victims, exclusively-owned
    # flushed pages spilled, shared pages retained) until `high` is free
    # again; a blocked admission parks on demand regardless of the
    # watermark. `prefix_sharing` turns on copy-on-write prompt-prefix
    # sharing: identical prompt prefixes (chained content hash, verified
    # bitwise on device before trust) map the same physical pages across
    # slots, and admission reserves only the unshared suffix.
    host_pool_pages: int | None = None
    host_pool_mb: float | None = None
    tier_watermarks: Any = (0.25, 0.5)
    prefix_sharing: bool = False

    def resolved_plan(self) -> plan_lib.CompressionPlan:
        """The per-layer plan (scalar kv_keep is a uniform-plan shim)."""
        return plan_lib.as_plan(self.plan, keep=self.kv_keep,
                                backend=self.codec_backend)

    @property
    def paged(self) -> bool:
        return self.pool_pages is not None or self.page_budget_mb is not None

    def resolved_pool_pages(self, cfg) -> int:
        """Page count of the pool: explicit, or solved from the byte budget
        with the plan's per-layer page size (a page spans every layer, so
        its size is the summed per-layer block-group bytes)."""
        if self.pool_pages is not None:
            return int(self.pool_pages)
        assert self.page_budget_mb is not None
        page_b = self.resolved_plan().page_bytes(cfg)
        pages = int(self.page_budget_mb * 1e6 // page_b)
        if pages < 1:
            raise ValueError(
                f"page_budget_mb={self.page_budget_mb} holds no page "
                f"(one page = {page_b} B across {cfg.n_layers} layers)")
        return pages

    @property
    def tiered(self) -> bool:
        return (self.host_pool_pages is not None
                or self.host_pool_mb is not None)

    def resolved_host_pages(self, cfg) -> int:
        """Host-tier page count: explicit, or solved from the MB budget with
        the same per-page byte size as the device pool (host pages mirror
        the packed/scale geometry exactly — tails are never paged)."""
        if self.host_pool_pages is not None:
            return int(self.host_pool_pages)
        assert self.host_pool_mb is not None
        page_b = self.resolved_plan().page_bytes(cfg)
        pages = int(self.host_pool_mb * 1e6 // page_b)
        if pages < 1:
            raise ValueError(
                f"host_pool_mb={self.host_pool_mb} holds no page "
                f"(one page = {page_b} B across {cfg.n_layers} layers)")
        return pages


def make_steps(api: ModelAPI, sc: ServeConfig):
    """(prefill_fn, decode_fn, cache_init, vec_pos). jit left to the caller.

    prefill_fn(params, tokens, lengths=None) -> (logits, cache)
    decode_fn(params, token, cache, pos)     -> (logits, cache)

    vec_pos=True marks families whose decode accepts a per-slot (B,)
    position vector — the requirement for continuous batching. Recurrent
    families (state caches, scalar step index) report False and are served
    wave-at-a-time. The classification lives on ArchConfig.vec_pos_decode
    (shared with ModelAPI.input_specs).
    """
    cfg = api.cfg
    use_comp = sc.kv_compress and cfg.attn_type == "gqa" and \
        cfg.resolved_head_dim % 8 == 0 and cfg.vec_pos_decode

    if sc.paged and not use_comp:
        raise ValueError(
            "paged KV pool needs kv_compress=True on a GQA family with "
            f"per-slot positions (arch {cfg.name}: attn_type={cfg.attn_type}, "
            f"vec_pos_decode={cfg.vec_pos_decode})")

    if use_comp and sc.paged:
        plan = sc.resolved_plan()
        n_pages = sc.resolved_pool_pages(cfg)

        def prefill_fn(params, tokens, lengths=None):
            return prefill_compressed_paged(params, tokens, cfg, plan=plan,
                                            lengths=lengths)

        def decode_fn(params, token, cache, pos, flush_page,
                      attend_blocks=None):
            return decode_step_compressed(params, token, cache, pos, cfg,
                                          kv_block=sc.kv_block,
                                          codec_backend=sc.codec_backend,
                                          flush_page=flush_page,
                                          attend_blocks=attend_blocks,
                                          pages_per_tile=sc.decode_tile_pages)

        cache_init = lambda b: kvc.init_paged_cache(cfg, b, sc.max_seq,
                                                    n_pages, plan=plan)
        return prefill_fn, decode_fn, cache_init, True

    if use_comp:
        plan = sc.resolved_plan()

        def prefill_fn(params, tokens, lengths=None):
            return prefill_compressed(params, tokens, cfg, sc.max_seq,
                                      plan=plan, lengths=lengths)

        def decode_fn(params, token, cache, pos):
            return decode_step_compressed(params, token, cache, pos, cfg,
                                          kv_block=sc.kv_block,
                                          codec_backend=sc.codec_backend)

        cache_init = lambda b: kvc.init_compressed_cache(cfg, b, sc.max_seq,
                                                         plan=plan)
        return prefill_fn, decode_fn, cache_init, True

    if cfg.vec_pos_decode:
        def prefill_fn(params, tokens, lengths=None):
            return T.prefill(params, tokens, cfg, sc.max_seq)

        def decode_fn(params, token, cache, pos):
            return T.decode_step(params, token, cache, pos, cfg, kv_block=sc.kv_block)

        cache_init = lambda b: api.init_cache(b, sc.max_seq)
        return prefill_fn, decode_fn, cache_init, True

    # recurrent families: prefill = teacher-forced decode of the prompt
    def prefill_fn(params, tokens, lengths=None):
        b, s = tokens.shape
        # cache activations must match the params' compute dtype
        cache = api.init_cache(b, sc.max_seq, dtype=params["embed"].dtype)

        def body(carry, t):
            cache = carry
            logits, cache = api.decode_step(params, tokens[:, t], cache, t)
            return cache, logits

        cache, logits_seq = jax.lax.scan(body, cache, jnp.arange(s))
        return jnp.moveaxis(logits_seq, 0, 1), cache  # (B, S, V)

    def decode_fn(params, token, cache, pos):
        return api.decode_step(params, token, cache, pos)

    cache_init = lambda b: api.init_cache(b, sc.max_seq)
    return prefill_fn, decode_fn, cache_init, False


def make_fused_steps(prefill_fn, decode_fn, sc: ServeConfig, *, paged: bool):
    """Fuse sampling into the jitted steps so only (B,) int32 tokens ever
    leave the device.

    admit_fn(params, tokens, lengths[, rng]) -> (first_tokens, slot_cache)
        packed admission: R right-padded prompts in one bucketed prefill;
        each row's first output token is sampled from its own last prompt
        position (lengths[r]-1) on device.
    step_fn(params, token, cache, pos[, flush_page][, rng])
        -> (next_token, pos+1, cache)
        one decode step with sampling fused; token/pos stay device-resident
        between steps — the per-token logits transfer and host argmax of
        the old loop are gone.

    Greedy (temperature<=0) takes no rng argument so its signature is
    stable for AOT warmup; temperature sampling threads a per-call PRNG key
    (host-split, so the stream is deterministic per step index).
    """
    greedy = sc.temperature <= 0.0

    @jax.named_scope("sample")
    def pick(logits, rng):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            rng, logits / sc.temperature, axis=-1).astype(jnp.int32)

    def admit_core(params, tokens, lengths, rng):
        logits, slot_cache = prefill_fn(params, tokens, lengths)
        rows = jnp.arange(tokens.shape[0])
        return pick(logits[rows, lengths - 1], rng), slot_cache

    if greedy:
        def admit_fn(params, tokens, lengths):
            return admit_core(params, tokens, lengths, None)

        if paged:
            def step_fn(params, token, cache, pos, flush_page,
                        attend_blocks=None):
                logits, cache = decode_fn(params, token, cache, pos, flush_page,
                                          attend_blocks=attend_blocks)
                return pick(logits, None), pos + 1, cache
        else:
            def step_fn(params, token, cache, pos):
                logits, cache = decode_fn(params, token, cache, pos)
                return pick(logits, None), pos + 1, cache
    else:
        def admit_fn(params, tokens, lengths, rng):
            return admit_core(params, tokens, lengths, rng)

        if paged:
            def step_fn(params, token, cache, pos, flush_page, rng,
                        attend_blocks=None):
                logits, cache = decode_fn(params, token, cache, pos, flush_page,
                                          attend_blocks=attend_blocks)
                return pick(logits, rng), pos + 1, cache
        else:
            def step_fn(params, token, cache, pos, rng):
                logits, cache = decode_fn(params, token, cache, pos)
                return pick(logits, rng), pos + 1, cache

    return admit_fn, step_fn


# ---------------------------------------------------------------------------
# Mesh placement: explicit NamedShardings for every serve step
# ---------------------------------------------------------------------------

def serve_shardings(api: ModelAPI, params: Params, sc: ServeConfig,
                    batch: int, cache_init) -> dict[str, Any]:
    """Explicit NamedShardings for the serve step functions on `sc.mesh`.

    Placement mirrors the train-path rules: params via `param_specs` with
    fsdp=False (TP on `model`, replicated across `data` — serving reads
    weights every step, FSDP re-gathers would dominate decode), the KV pool
    via `cache_specs` (batch slots on `data`, kv heads on `model`, every
    `KVSegment` leaf included), and (B,) token/position vectors on `data`.
    Single-request admission tensors (batch 1) replicate — `fit_spec` drops
    non-dividing axes — and splice into the sharded pool through the
    slot-write scatter, so admitting one request never reshards the pool.
    """
    mesh = sc.mesh
    cfg = api.cfg
    axes = tuple(mesh.axis_names)
    ns = lambda spec: NamedSharding(mesh, spec)
    pool_shapes = jax.eval_shape(lambda: cache_init(batch))
    slot_shapes = jax.eval_shape(lambda: cache_init(1))
    return {
        "params": sh.param_shardings(params, mesh, fsdp=False),
        "rep": ns(P()),
        # (B,) per-slot token/position vectors ride the slot-pool data axes
        # — including the fused step's sampled-token and pos+1 OUTPUTS, the
        # only tensors the async loop ever reads back
        "vec": sh.step_vec_sharding(mesh, batch),
        "pool": sh.cache_shardings(pool_shapes, cfg, mesh),
        "slot": sh.cache_shardings(slot_shapes, cfg, mesh),
        "tokens": ns(sh.data_batch_spec(axes, 2, dim0=batch, mesh=mesh)),
        "logits_decode": ns(sh.data_batch_spec(axes, 2, dim0=batch, mesh=mesh)),
        "logits_prefill": ns(sh.data_batch_spec(axes, 3, dim0=batch, mesh=mesh)),
        "logits_admit": ns(sh.data_batch_spec(axes, 3, dim0=1, mesh=mesh)),
    }


# ---------------------------------------------------------------------------
# Slot lifecycle helpers (jit-able; work on any cache pytree, batch axis 1)
# ---------------------------------------------------------------------------

def cache_write_slot(cache, slot_cache, slot: jax.Array):
    """Copy a single-request (batch=1) cache into slot `slot` of the pool."""
    return jax.tree.map(
        lambda c, s: jax.lax.dynamic_update_slice_in_dim(
            c, s.astype(c.dtype), slot, axis=1),
        cache, slot_cache,
    )


def cache_write_rows(cache, rows_cache, slots: jax.Array):
    """Scatter an R-row packed-admission cache into slots `slots` of the
    pool (any dense cache pytree, batch axis 1).  Rows the admission group
    padded to a warmed row count carry out-of-range slot ids (>= B) and are
    dropped — a padding row can land nowhere."""
    return jax.tree.map(
        lambda c, s: c.at[:, slots].set(s.astype(c.dtype), mode="drop"),
        cache, rows_cache,
    )


def token_fix(token, pos, idx, tok_vals, pos_vals):
    """Scatter admission/retirement corrections into the device-resident
    (B,) token/pos state between decode steps.  `idx` is padded to a fixed
    (B,) with out-of-range entries (dropped) so the fix compiles once."""
    return (token.at[idx].set(tok_vals, mode="drop"),
            pos.at[idx].set(pos_vals, mode="drop"))


def cache_reset_slot(cache, slot: jax.Array):
    """Zero one slot's planes on retirement (any cache pytree)."""
    return kvc.cache_reset_slot(cache, slot)


# ---------------------------------------------------------------------------
# Continuous-batching engine
# ---------------------------------------------------------------------------

@dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (S,) int32
    max_new: int
    out_tokens: list[int] = field(default_factory=list)
    done: bool = False


@dataclass
class _ParkedSlot:
    """Host-side record of an evicted (parked) live slot.

    `blocks[j]` carries block j's tier bit for every FLUSHED block:
    ("host", host_page_id) for exclusively-owned pages spilled to the
    TierManager, ("device", page_id) for shared pages that stayed
    device-resident (their refcount includes this record's reference).
    `token`/`pos` are the saved device row state `_apply_fix` replays at
    resume; `tails` (the raw 8-token remainder, per segment) is filled in
    by the BackgroundWorker alongside the host copies."""
    req: Request
    token: int
    pos: int
    horizon_blocks: int           # worst-case pages to re-reserve at resume
    shared: int                   # _slot_shared at park time
    keys: list                    # _slot_keys at park time
    blocks: list                  # per flushed block: (tier, id)
    tails: Any = None


class Engine:
    """Continuous-batching request server over a shared KV pool.

    Slots are independent: each live request has its own position, so a
    retired slot is re-admitted immediately from the queue while its
    neighbours keep decoding — no request waits for the wave's slowest.
    Admission packs every free slot's request into ONE prefill call at a
    fixed ladder bucket (prompts rounded up to AOT-compiled prompt-length
    buckets — `pipeline.PrefillLadder`; `aot_warmup=True` compiles the
    whole ladder at construction so nothing compiles under traffic) and
    splices each row into its slot; live slots are never re-prefilled.

    Sampling is fused into the jitted prefill/decode steps, so only the
    `(B,)` sampled-token vector ever crosses to the host; `token`/`pos`
    stay device-resident between steps. With `async_host=True` the loop
    runs one step deep — step t+1 is dispatched before step t's tokens are
    read — and bookkeeping (token appends, latency, page returns) drains
    on a background thread. Greedy outputs are bitwise the synchronous
    serial loop's (tests/test_serve_pipeline.py).

    Sampling order is explicit: the first output token is sampled from the
    prefill logits at the prompt's last position; a decode step only runs
    while some slot still needs tokens (a request whose max_new is 1
    finishes at admission without a decode step). `latency_stats()`
    reports p50/p99 TTFT and inter-token latency.

    The serve loop is traced by host spans (`pipeline.Span`, integer
    metadata), which land in a running profile on the device ops' clock:
    `serve.admit` (rows, bucket) holds `serve.prefill` (rows, bucket),
    `serve.worker_flush` and, on a tiered pool, `serve.park` (slot) /
    `serve.resume`; `serve.dispatch` (step, live, bucket) launches decode
    step `step`, `serve.readback` (step) blocks on it (or, inside
    `serve.admit`, on the admission's first tokens) and `serve.process`
    (step) applies its bookkeeping; `serve.bookkeeping` is one closure on
    the bookkeeping thread and `python.gc` one collector pause. The time
    counters of `stats` are sums of span durations, each added when its
    span closes, so a window's delta can be read while `generate()` runs:
    prefill_s = Σ serve.admit, decode_s = Σ serve.dispatch + Σ decode
    serve.readback, wait_s = Σ serve.readback (the host blocked on the
    device). host_s is written only when a continuous `generate()`
    returns: the call's wall time less its decode_s and prefill_s.
    warmup_s is the AOT warmup at construction.

    `scheduler="static"` restores wave-at-a-time lock-step batching
    (right-aligned prompts, one scalar position) — the baseline the
    throughput benchmark compares against. Families without per-slot
    position support (recurrent state caches) always use it.
    """

    def __init__(self, api: ModelAPI, params: Params, sc: ServeConfig, batch: int,
                 seed: int = 0, scheduler: str = "continuous"):
        assert scheduler in ("continuous", "static"), scheduler
        self.api = api
        self.sc = sc
        self.batch = batch
        self.rng = jax.random.PRNGKey(seed)
        prefill_fn, decode_fn, cache_init, vec_pos = make_steps(api, sc)
        self.vec_pos = vec_pos
        self.scheduler = scheduler if vec_pos else "static"
        self.paged = sc.paged
        if self.paged:
            if self.scheduler != "continuous":
                raise ValueError("paged KV pool requires the continuous "
                                 "scheduler (pages follow per-slot lifetimes)")
            # host-side page allocator: the free list IS the allocation
            # policy — the device only ever sees page ids it was handed
            self._n_pages = sc.resolved_pool_pages(api.cfg)
            self._free_pages = list(range(self._n_pages))
            self._slot_pages: list[list[int]] = [[] for _ in range(batch)]
            # copy-on-write sharing makes a free-list entry a refcount-zero
            # page rather than a never-referenced one; every release goes
            # through _release_page_list so a page frees exactly once, when
            # its LAST reference drops
            self._page_refs = np.zeros(self._n_pages, np.int64)
            self._slot_shared = [0] * batch   # leading shared blocks per slot
            self._slot_keys: list[list[bytes]] = [[] for _ in range(batch)]
            self._slot_seq = [0] * batch      # admission order (victim pick)
            self._admit_seq = 0
        self._parked: dict[int, _ParkedSlot] = {}
        self._park_order: list[int] = []
        self._tier = None
        self._prefix = None
        self.paranoid_pool_checks = False
        if (sc.tiered or sc.prefix_sharing) and not self.paged:
            raise ValueError("host_pool_pages/host_pool_mb/prefix_sharing "
                             "require the paged KV pool (set pool_pages or "
                             "page_budget_mb)")
        if sc.tiered:
            self._tier = tiering.TierManager(
                jax.eval_shape(lambda: cache_init(batch)),
                sc.resolved_host_pages(api.cfg))
            lo, hi = sc.tier_watermarks
            assert 0.0 <= float(lo) <= float(hi) <= 1.0, sc.tier_watermarks
            self._wm_low = int(float(lo) * self._n_pages)
            self._wm_high = max(int(float(hi) * self._n_pages), self._wm_low)
        if sc.prefix_sharing:
            self._prefix = tiering.PrefixIndex()
        self._cache_init_raw = cache_init  # un-jitted: pool accounting
        self._vec_sharding = None  # (B,) token/pos placement under a mesh
        self.trace_counts = pl.TraceCounts()
        tc = self.trace_counts
        if self.scheduler == "continuous":
            # fused-sampling steps: only (B,) int32 tokens cross to the host
            self.ladder = pl.PrefillLadder.build(sc.max_seq, sc.prefill_buckets)
            admit_fn, step_fn = make_fused_steps(prefill_fn, decode_fn, sc,
                                                 paged=self.paged)
            admit_fn = pl.counting("prefill", tc, admit_fn)
            step_fn = pl.counting("decode", tc, step_fn)
            if self.paged:
                self.decode_ladder = pl.DecodeLadder.build(sc.max_seq,
                                                           sc.decode_buckets)
                # one partial per bucket: each binds its static table-slice
                # width, so each is a distinct jit (and a distinct "decode"
                # trace — the warmed count is len(buckets)); each keeps the
                # step's name, which names its program in a profile
                bucket_fns = {}
                for t in self.decode_ladder.buckets:
                    fn = functools.partial(step_fn,
                                           attend_blocks=t // kvc.BLOCK)
                    fn.__name__ = step_fn.__name__
                    bucket_fns[t] = fn
            write_fn = pl.counting(
                "write", tc,
                kvc.paged_write_rows if self.paged else cache_write_rows)
            reset_fn = pl.counting(
                "reset", tc,
                kvc.paged_reset_slot if self.paged else cache_reset_slot)
            fix_fn = pl.counting("fix", tc, token_fix)
            # on a TPU the paged pool (argument 2 of the decode step) is
            # donated, so the step's in-place flush needs no whole-pool copy
            # at the program boundary (the serve loop threads the cache
            # linearly); elsewhere a step leaves its input cache readable
            donate = (2,) if jax.default_backend() == "tpu" else ()
            if sc.mesh is None:
                self._admit_step = jax.jit(admit_fn)
                if self.paged:
                    self._decode_fns = {t: jax.jit(fn, donate_argnums=donate)
                                        for t, fn in bucket_fns.items()}
                    self._decode = self._decode_fns[
                        self.decode_ladder.buckets[-1]]
                else:
                    self._decode = jax.jit(step_fn)
                self._cache_init = cache_init
                self._write = jax.jit(write_fn)
                self._reset = jax.jit(reset_fn)
                self._fix = jax.jit(fix_fn)
                if self._tier is not None:
                    self._spill = jax.jit(
                        pl.counting("spill", tc, kvc.paged_gather_slot))
                    self._restore = jax.jit(
                        pl.counting("restore", tc, kvc.paged_write_slot))
                if self._prefix is not None:
                    self._match = jax.jit(
                        pl.counting("match", tc, kvc.paged_rows_match))
            else:
                shd = serve_shardings(api, params, sc, batch, cache_init)
                self._vec_sharding = shd["vec"]
                # place params once; the decode jit pins the same shardings,
                # so no per-call retransfer
                params = jax.device_put(params, shd["params"])
                dec_in = [shd["params"], shd["vec"], shd["pool"], shd["vec"]]
                if self.paged:
                    dec_in.append(shd["vec"])
                if sc.temperature > 0.0:
                    dec_in.append(shd["rep"])
                dec_out = (shd["vec"], shd["vec"], shd["pool"])
                if self.paged:
                    # every bucket shares the full-capacity step's shardings:
                    # inputs are shape-identical across buckets (the table
                    # slice is internal and static), so the jit cache keys
                    # only on the bound slice width
                    self._decode_fns = {
                        t: jax.jit(fn, in_shardings=tuple(dec_in),
                                   out_shardings=dec_out,
                                   donate_argnums=donate)
                        for t, fn in bucket_fns.items()}
                    self._decode = self._decode_fns[
                        self.decode_ladder.buckets[-1]]
                else:
                    self._decode = jax.jit(
                        step_fn, in_shardings=tuple(dec_in),
                        out_shardings=dec_out,
                    )
                # admission tensors are bucket-shaped (rows x bucket varies
                # across the warmed ladder), so the admit step rides
                # placement propagation off the committed params; the
                # splice/reset/fix jits pin the pool and (B,) state
                self._admit_step = jax.jit(admit_fn)
                pool_init = jax.jit(
                    pl.counting("cache_init", tc, lambda: cache_init(batch)),
                    out_shardings=shd["pool"])
                self._cache_init = lambda b: pool_init()
                self._write = jax.jit(write_fn, out_shardings=shd["pool"])
                self._reset = jax.jit(reset_fn, out_shardings=shd["pool"])
                if self._tier is not None:
                    # host pages live OUTSIDE the mesh: the spill gather
                    # lands replicated (one host copy reads it whole), and
                    # the restore takes the replicated host tree back in
                    # with the pool's NamedSharding pinned on the output
                    upd_shapes = jax.eval_shape(
                        kvc.paged_gather_slot,
                        jax.eval_shape(lambda: cache_init(batch)),
                        jax.ShapeDtypeStruct((), jnp.int32),
                        jax.ShapeDtypeStruct((1,), jnp.int32))
                    rep_upd = sh.host_transfer_shardings(upd_shapes, sc.mesh)
                    self._spill = jax.jit(
                        pl.counting("spill", tc, kvc.paged_gather_slot),
                        in_shardings=(shd["pool"], shd["rep"], shd["rep"]),
                        out_shardings=rep_upd)
                    self._restore = jax.jit(
                        pl.counting("restore", tc, kvc.paged_write_slot),
                        in_shardings=(shd["pool"], rep_upd, shd["rep"],
                                      shd["rep"], shd["rep"]),
                        out_shardings=shd["pool"])
                if self._prefix is not None:
                    self._match = jax.jit(
                        pl.counting("match", tc, kvc.paged_rows_match),
                        out_shardings=shd["rep"])
                self._fix = jax.jit(
                    fix_fn,
                    in_shardings=(shd["vec"], shd["vec"], shd["rep"],
                                  shd["rep"], shd["rep"]),
                    out_shardings=(shd["vec"], shd["vec"]),
                )
        elif sc.mesh is None:
            self._prefill = jax.jit(prefill_fn)
            self._decode = jax.jit(decode_fn)
            self._cache_init = cache_init
        else:
            shd = serve_shardings(api, params, sc, batch, cache_init)
            params = jax.device_put(params, shd["params"])
            # lock-step wave: the full (B, S) prompt block is data-sharded
            # and decode runs on one scalar (replicated) position
            self._decode = jax.jit(
                decode_fn,
                in_shardings=(shd["params"], shd["vec"], shd["pool"],
                              shd["rep"]),
                out_shardings=(shd["logits_decode"], shd["pool"]),
            )
            self._prefill = jax.jit(
                prefill_fn,
                in_shardings=(shd["params"], shd["tokens"]),
                out_shardings=(shd["logits_prefill"], shd["pool"]),
            )
            pool_init = jax.jit(lambda: cache_init(batch),
                                out_shardings=shd["pool"])
            self._cache_init = lambda b: pool_init()
        self.params = params
        self.stats = {"requests": 0, "tokens_out": 0, "steps": 0,
                      "prefill_s": 0.0, "decode_s": 0.0, "host_s": 0.0,
                      "wait_s": 0.0, "warmup_s": 0.0,
                      "slot_steps_live": 0, "slot_steps_total": 0,
                      "peak_live_slots": 0, "admit_blocked_on_pages": 0,
                      "peak_pages_in_use": 0, "decode_bucket_tokens": 0,
                      "pages_spilled": 0, "pages_restored": 0,
                      "slots_parked": 0, "slots_resumed": 0,
                      "prefix_shared_blocks": 0, "prefix_demotions": 0}
        self._lat = {"ttft_s": [], "itl_s": []}
        self._staged = []
        self._worker = None
        self._t_gen0 = 0.0
        if sc.aot_warmup and self.scheduler == "continuous":
            ctx = jax.set_mesh(sc.mesh) if sc.mesh is not None \
                else contextlib.nullcontext()
            with ctx:
                self.stats["warmup_s"] += pl.warmup_engine(self)

    # ------------------------------------------------------------------ util
    def device_vec(self, host: np.ndarray) -> jax.Array:
        """A host (B,) token/position vector placed as the decode and fix
        jits emit theirs: committed on the mesh's data axes under a mesh,
        uncommitted on one device otherwise.  Warmup and traffic then hand
        those jits identically placed arguments, so the warmed executables
        are reused (a jnp constructor under `jax.set_mesh` would commit it
        replicated, which a jit pinned to the data axes refuses)."""
        if self._vec_sharding is None:
            return jnp.asarray(host)
        return jax.device_put(host, self._vec_sharding)

    def _sample(self, logits: jax.Array) -> jax.Array:
        if self.sc.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.rng, sub = jax.random.split(self.rng)
        return jax.random.categorical(sub, logits / self.sc.temperature, axis=-1).astype(jnp.int32)

    def slot_utilization(self) -> float:
        """Fraction of decode slot-steps spent on live requests."""
        return self.stats["slot_steps_live"] / max(self.stats["slot_steps_total"], 1)

    def latency_stats(self) -> dict:
        """p50/p99 TTFT and inter-token latency (seconds) over everything
        this engine has served.  TTFT = generate() entry to the request's
        first token leaving the device (admission queueing included); ITL =
        gap between a slot's consecutive token emissions on the host clock
        (pipeline bubbles included).  Zeros when nothing was served."""
        out = {}
        for key, name in (("ttft_s", "ttft"), ("itl_s", "itl")):
            vals = self._lat[key]
            for q in (50, 99):
                out[f"{name}_p{q}_s"] = \
                    float(np.percentile(vals, q)) if vals else 0.0
        return out

    def kv_pool_stats(self) -> dict:
        """Analytic footprint of this engine's KV pool: total bytes and the
        per-device slice under `sc.mesh` (the banked-buffer accounting —
        what one device/bank actually holds). No allocation: eval_shape.

        On a paged engine the report adds the allocator's view: pool pages,
        page bytes, pages currently and peak in use, and slots-per-GB (how
        many concurrent slots one GB of pool supports at this geometry —
        the headline number the paged pool improves)."""
        shapes = jax.eval_shape(lambda: self._cache_init_raw(self.batch))
        total = sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                    for l in jax.tree.leaves(shapes))
        mesh = self.sc.mesh
        per_device = float(total) if mesh is None else sh.per_device_bytes(
            shapes, sh.cache_specs(shapes, self.api.cfg, mesh), mesh)
        out = {"kv_pool_bytes": int(total),
               "kv_bytes_per_device": per_device,
               "slots_per_gb": self.batch / max(total / 1e9, 1e-12)}
        if "measured_kv_bytes" in self.stats:
            # recorded by _run_continuous after its queue drains: the
            # data-dependent footprint per the codec families' measured
            # per-tile accounting, vs the analytic pool above
            out["measured_kv_bytes"] = float(self.stats["measured_kv_bytes"])
        if self.paged:
            if self._worker is not None:
                # settle in-flight retirements/spills so the counts (and
                # the invariant check below) see a quiescent allocator
                self._worker.flush()
            refs = self._page_refs
            out.update(
                pool_pages=self._n_pages,
                page_bytes=self.sc.resolved_plan().page_bytes(self.api.cfg),
                pages_in_use=self._n_pages - len(self._free_pages),
                pages_device_free=len(self._free_pages),
                peak_pages_in_use=self.stats["peak_pages_in_use"],
                shared_physical_pages=int((refs > 1).sum()),
                shared_extra_refs=int((refs[refs > 1] - 1).sum()),
                prefix_shared_blocks=self.stats["prefix_shared_blocks"],
                prefix_demotions=self.stats["prefix_demotions"],
            )
            if self._tier is not None:
                out.update(
                    host_pool_pages=self._tier.host_pages,
                    host_pool_bytes=self._tier.nbytes(),
                    pages_host_in_use=self._tier.in_use,
                    pages_host_free=self._tier.free_pages,
                    pages_spilled=self.stats["pages_spilled"],
                    pages_restored=self.stats["pages_restored"],
                    slots_parked=self.stats["slots_parked"],
                    slots_resumed=self.stats["slots_resumed"],
                )
            self.check_page_invariants()
        return out

    def check_page_invariants(self) -> None:
        """Allocator conservation — the tiered pool's ledger must balance:

            device_in_use + device_free + host_resident + host_free
                == pool_pages + host_pool_pages

        refcount-weighted on the device side: every free-list page has
        refcount 0, every held page's refcount equals the number of (slot,
        block) references to it across live, staged, and parked slots, and
        every host page is either free or holds exactly one parked block.
        Pure host-list arithmetic (no device sync); runs on every
        kv_pool_stats() call and — with `paranoid_pool_checks` set — after
        every admission flush and retirement, which is how the tests catch
        the page-leak bug class the PR-5 rollback fix closed."""
        if not self.paged:
            return
        free = self._free_pages
        assert len(free) == len(set(free)), "free list has duplicates"
        held = collections.Counter()
        host_held: list[int] = []
        for pages in self._slot_pages:
            held.update(pages)
        for rec in self._parked.values():
            for tier, ref in rec.blocks:
                if tier == "host":
                    host_held.append(ref)
                else:
                    held.update([ref])
        refs = self._page_refs
        for p in free:
            assert refs[p] == 0, f"free page {p} has refcount {int(refs[p])}"
        overlap = set(free) & set(held)
        assert not overlap, f"pages both free and held: {sorted(overlap)}"
        for p, n in held.items():
            assert refs[p] == n, \
                f"page {p}: refcount {int(refs[p])} != {n} references"
        assert int((refs > 0).sum()) == len(held)
        assert int(refs.sum()) == sum(held.values())
        assert len(free) + len(held) == self._n_pages, \
            (len(free), len(held), self._n_pages)
        if self._tier is not None:
            assert len(host_held) == len(set(host_held)), \
                "host page referenced by two parked blocks"
            assert self._tier.in_use == len(host_held), \
                (self._tier.in_use, sorted(host_held))
            assert (len(held) + len(free) + len(host_held)
                    + self._tier.free_pages
                    == self._n_pages + self._tier.host_pages)

    # ------------------------------------------------------------------ API
    def generate(self, requests: list[Request]) -> list[Request]:
        """Serve every request to completion; returns them in input order.

        The caller's list is never mutated; the Request objects are (their
        out_tokens/done fields fill in as slots retire).
        """
        queue = list(requests)
        self._t_gen0 = time.perf_counter()
        wall0 = time.time_ns()
        d0, p0 = self.stats["decode_s"], self.stats["prefill_s"]
        # the ambient mesh context activates the model-internal shard hints
        # (sharding.logical / attn_hint) while the jits' explicit in/out
        # NamedShardings pin the step boundaries
        ctx = jax.set_mesh(self.sc.mesh) if self.sc.mesh is not None \
            else contextlib.nullcontext()
        with ctx, pl.GcSpans():
            if self.scheduler == "static":
                for w0 in range(0, len(queue), self.batch):
                    self._run_wave(queue[w0:w0 + self.batch])
            else:
                self._run_continuous(queue)
                # honest attribution: whatever this call's wall time was not
                # spent dispatching/waiting on the device is host overhead
                wall = (time.time_ns() - wall0) * 1e-9
                self.stats["host_s"] += wall \
                    - (self.stats["decode_s"] - d0) \
                    - (self.stats["prefill_s"] - p0)
        self.stats["requests"] += len(queue)
        return queue

    # ------------------------------------------------- continuous scheduler
    def _pages_needed(self, r: Request) -> int:
        """Worst-case pages request `r` can flush over its whole lifetime.

        Positions written span [0, min(plen + max_new - 1, max_seq)); a page
        is consumed per completed 8-token block, so reserving this many at
        admission guarantees a live slot never stalls mid-decode for a page
        (slot preemption is the ROADMAP follow-on that would relax this).
        """
        horizon = min(len(r.prompt) + r.max_new - 1, self.sc.max_seq)
        return horizon // kvc.BLOCK

    def _release_page_list(self, pages) -> None:
        """Drop one reference per listed page; a page rejoins the free list
        (and leaves the prefix index) when its LAST reference drops — the
        copy-on-write half of prefix sharing. Append-in-list-order keeps
        the free-list sequence identical to the pre-refcount `extend` when
        nothing is shared, so page-id determinism is preserved. Runs on
        the serve thread or the worker; the engine's flush-before-reserve
        barrier keeps the two from interleaving with allocation."""
        for p in pages:
            n = self._page_refs[p] = self._page_refs[p] - 1
            assert n >= 0, f"page {p} over-released"
            if n == 0:
                self._free_pages.append(p)
                if self._prefix is not None:
                    self._prefix.drop_page(p)

    def _release_pages(self, slot: int) -> None:
        pages, self._slot_pages[slot] = self._slot_pages[slot], []
        self._slot_shared[slot] = 0
        self._slot_keys[slot] = []
        self._release_page_list(pages)

    def _reserve_pages(self, r: Request, slot: int) -> bool:
        """Reserve `slot`'s worst-case page horizon for `r`; False = blocked
        on free pages (admission keeps the request queued, FCFS).

        With prefix sharing on, the longest leading run of FULL prompt
        blocks whose content keys already name device-resident pages is
        mapped by reference — those pages' refcounts bump and only the
        unshared suffix draws from the free list, which is the
        admission-cost collapse for common-system-prompt traffic. The
        shared run is only a candidate here: `_flush_admissions` verifies
        it bitwise on device and demotes any mismatch to fresh pages."""
        horizon = self._pages_needed(r)
        if horizon > self._n_pages:
            raise ValueError(
                f"request {r.uid} needs {horizon} pages > pool of "
                f"{self._n_pages} (raise pool_pages/page_budget_mb"
                " or lower max_new)")
        shared: list[int] = []
        keys: list[bytes] = []
        if self._prefix is not None:
            keys = self._prefix.key_fn(np.asarray(r.prompt, np.int32))
            shared = self._prefix.lookup_run(keys)[:horizon]
        if horizon - len(shared) > len(self._free_pages):
            return False
        own = [self._free_pages.pop() for _ in range(horizon - len(shared))]
        for p in shared:
            self._page_refs[p] += 1
        for p in own:
            assert self._page_refs[p] == 0, f"free page {p} had references"
            self._page_refs[p] = 1
        self._slot_pages[slot] = shared + own
        self._slot_shared[slot] = len(shared)
        self._slot_keys[slot] = keys
        if self._prefix is not None:
            # register own FULL prompt blocks immediately so later rows of
            # the same admission group already share them (still verified
            # bitwise post-splice like any other candidate)
            for j in range(len(shared), len(r.prompt) // kvc.BLOCK):
                self._prefix.register(keys[j], self._slot_pages[slot][j])
            self.stats["prefix_shared_blocks"] += len(shared)
        used = self._n_pages - len(self._free_pages)
        self.stats["peak_pages_in_use"] = max(
            self.stats["peak_pages_in_use"], used)
        return True

    def _admit(self, r: Request, cache, slot: int):
        """Stage one request into `slot` (pages already reserved): bucket
        its prompt on the AOT ladder and queue the row for the admission
        group's single prefill call (`_flush_admissions`).  An off-ladder
        prompt raises here — admission never compiles a fresh bucket under
        traffic — and the scheduler rolls the page reservation back."""
        plen = len(r.prompt)
        self._staged.append((r, slot, plen, self.ladder.bucket_for(plen)))
        return cache

    def _flush_admissions(self, cache):
        """Run the staged admission group: ONE prefill call at the group's
        widest ladder bucket (rows padded to a warmed row count), one
        batched splice into the slots/pages, first tokens sampled on device
        at each row's own last prompt position."""
        if not self._staged:
            return cache
        staged, self._staged = self._staged, []
        bucket = max(b for (_, _, _, b) in staged)
        self._admitted[0] += len(staged)
        self._admitted[1] = max(self._admitted[1], bucket)
        rows = self.ladder.pad_rows(len(staged), self.batch)
        tokens = np.zeros((rows, bucket), np.int32)
        lengths = np.full(rows, bucket, np.int32)
        slot_ids = np.full(rows, self.batch, np.int32)  # padding rows drop
        for j, (r, slot, plen, _) in enumerate(staged):
            tokens[j, :plen] = r.prompt
            lengths[j] = plen
            slot_ids[j] = slot
        args = [self.params, jnp.asarray(tokens), jnp.asarray(lengths)]
        if self.sc.temperature > 0.0:
            self.rng, sub = jax.random.split(self.rng)
            args.append(sub)
        with pl.Span("serve.prefill", rows=len(staged), bucket=bucket):
            first, rows_cache = self._admit_step(*args)
            if self.paged:
                # splice through the block table: each row's full prompt
                # blocks land in its slot's reserved pages; bucket padding
                # blocks (and whole padding rows) carry out-of-range ids the
                # device scatter drops. Nothing max_seq-sized is written.
                page_ids = np.full((rows, bucket // kvc.BLOCK), self._n_pages,
                                   np.int32)
                table = np.zeros((rows, self.sc.max_seq // kvc.BLOCK),
                                 np.int32)
                for j, (r, slot, plen, _) in enumerate(staged):
                    pb = plen // kvc.BLOCK
                    pages = self._slot_pages[slot]
                    # shared prefix blocks are NOT rewritten (their ids stay
                    # out-of-range so the scatter drops them) — that is the
                    # copy-on-write contract; the table still maps them so
                    # the attend reads the shared pages
                    sh_n = self._slot_shared[slot]
                    page_ids[j, sh_n:pb] = pages[sh_n:pb]
                    table[j, :pb] = pages[:pb]
                cache = self._write(cache, rows_cache, jnp.asarray(slot_ids),
                                    jnp.asarray(page_ids), jnp.asarray(table))
                if self._prefix is not None and any(
                        self._slot_shared[s] for (_, s, _, _) in staged):
                    cache = self._verify_shared(cache, staged, rows_cache,
                                                rows, bucket, slot_ids)
            else:
                cache = self._write(cache, rows_cache, jnp.asarray(slot_ids))
        with pl.Span("serve.readback", self.stats, ("wait_s",),
                     step=self.stats["steps"]):
            firsts = np.asarray(first)
        t_emit = time.perf_counter()
        fix_i, fix_t, fix_p = [], [], []
        for j, (r, slot, plen, _) in enumerate(staged):
            tok = int(firsts[j])
            self.stats["tokens_out"] += 1
            finished = tok == self.sc.eos_id or r.max_new <= 1 \
                or plen >= self.sc.max_seq
            pages = None
            if finished:  # finished at admission — no decode step
                cache = self._reset(cache, jnp.int32(slot))
                if self.paged:
                    pages, self._slot_pages[slot] = self._slot_pages[slot], []
                    self._slot_shared[slot] = 0
                    self._slot_keys[slot] = []
            else:
                self._slots[slot] = r
                self._pos[slot] = plen
                self._nout[slot] = 1
                if self.paged:
                    self._admit_seq += 1
                    self._slot_seq[slot] = self._admit_seq
                    self._last_tok[slot] = tok
                fix_i.append(slot)
                fix_t.append(tok)
                fix_p.append(plen)
            self._worker.submit(functools.partial(
                self._bk_first, r, tok, t_emit - self._t_gen0, finished,
                pages, slot, t_emit))
        if fix_i:
            self._apply_fix(fix_i, fix_t, fix_p)
        if self.paged and self.paranoid_pool_checks:
            self._worker.flush()
            self.check_page_invariants()
        return cache

    def _verify_shared(self, cache, staged, rows_cache, rows, bucket,
                       slot_ids):
        """Bitwise-verify every shared-prefix candidate block on device and
        demote mismatches (copy-on-write fallback).

        Each admitted row computed its own K/V for its whole prompt, so the
        shared pages it was mapped to must equal the row's freshly computed
        blocks exactly — `paged_rows_match` compares on device without
        pulling page planes to the host. A mismatch (hash collision, by
        construction) demotes that block and every later shared block to
        fresh pages via ONE corrective splice at the same warmed
        rows x bucket shape, so sharing can only ever be a storage win,
        never an output change — and never a new jit trace."""
        nbv = bucket // kvc.BLOCK
        ver_ids = np.full((rows, nbv), self._n_pages, np.int32)
        for j, (r, slot, plen, _) in enumerate(staged):
            sh_n = self._slot_shared[slot]
            ver_ids[j, :sh_n] = self._slot_pages[slot][:sh_n]
        ok = np.asarray(self._match(cache, rows_cache, jnp.asarray(ver_ids)))
        page2 = np.full((rows, nbv), self._n_pages, np.int32)
        table2 = np.zeros((rows, self.sc.max_seq // kvc.BLOCK), np.int32)
        dirty = False
        for j, (r, slot, plen, _) in enumerate(staged):
            sh_n = self._slot_shared[slot]
            bad = [jj for jj in range(sh_n) if not ok[j, jj]]
            if bad:
                pages = self._slot_pages[slot]
                for jj in range(bad[0], sh_n):
                    if not self._free_pages:
                        raise RuntimeError(
                            "prefix-share demotion needs a free page and "
                            "the pool is empty — raise pool_pages")
                    old = pages[jj]
                    new = self._free_pages.pop()
                    self._release_page_list([old])
                    assert self._page_refs[new] == 0, new
                    self._page_refs[new] = 1
                    pages[jj] = new
                    page2[j, jj] = new
                self._slot_shared[slot] = bad[0]
                self.stats["prefix_demotions"] += sh_n - bad[0]
                dirty = True
            pb = plen // kvc.BLOCK
            table2[j, :pb] = self._slot_pages[slot][:pb]
        if dirty:
            cache = self._write(cache, rows_cache, jnp.asarray(slot_ids),
                                jnp.asarray(page2), jnp.asarray(table2))
            used = self._n_pages - len(self._free_pages)
            self.stats["peak_pages_in_use"] = max(
                self.stats["peak_pages_in_use"], used)
        return cache

    def _bk_first(self, r, tok, ttft, finished, pages, slot, t_emit):
        """Background bookkeeping for an admitted request's first token."""
        r.out_tokens.append(tok)
        self._lat["ttft_s"].append(ttft)
        self._last_emit[slot] = t_emit
        if finished:
            r.done = True
            if pages:
                self._release_page_list(pages)

    def _bk_step(self, emitted, retired, t_emit):
        """Background bookkeeping for one processed decode step: token
        appends + inter-token latency, then retirements (done flags and
        page returns, in slot order — the free-list sequence matches the
        synchronous loop's)."""
        for r, tok, slot in emitted:
            r.out_tokens.append(tok)
            self._lat["itl_s"].append(t_emit - self._last_emit[slot])
            self._last_emit[slot] = t_emit
        for r, pages in retired:
            r.done = True
            if pages:
                self._release_page_list(pages)

    def _apply_fix(self, idx, tok_vals, pos_vals):
        """Scatter admission/retirement corrections into the device-resident
        token/pos vectors (padded to one fixed (B,) shape)."""
        b = self.batch
        ii = np.full(b, b, np.int32)
        tv = np.zeros(b, np.int32)
        pv = np.zeros(b, np.int32)
        ii[:len(idx)] = idx
        tv[:len(idx)] = tok_vals
        pv[:len(idx)] = pos_vals
        self._tok_dev, self._pos_dev = self._fix(
            self._tok_dev, self._pos_dev, jnp.asarray(ii), jnp.asarray(tv),
            jnp.asarray(pv))
        self._devpos[np.asarray(idx, np.int64)] = pos_vals

    def _admit_free_slots(self, queue, cache):
        """One `serve.admit` span (rows admitted, widest bucket) around
        `_fill_free_slots`; its duration is added to stats["prefill_s"]."""
        self._admitted = [0, 0]
        with pl.Span("serve.admit", self.stats, ("prefill_s",)) as span:
            cache = self._fill_free_slots(queue, cache)
            span.set_metadata(rows=self._admitted[0],
                              bucket=self._admitted[1])
        return cache

    def _fill_free_slots(self, queue, cache):
        """Fill free slots from the queue (paged pools additionally gate on
        free pages, FCFS) and flush the staged group through one packed
        prefill (`packed_admission=False` caps the group at 1 — the serial
        baseline). A tiered pool first resumes parked slots — their
        requests are older than anything still queued, so they outrank new
        admissions — then runs the watermark policy: queued demand with
        free pages under the low mark parks cold slots until the high mark
        is free again, and a still-blocked reservation parks on demand."""
        group_cap = self.batch if self.sc.packed_admission else 1
        want = self._qi < len(queue) and any(s is None for s in self._slots)
        if self.paged and (want or self._parked):
            # deterministic allocator: apply every pending retirement's page
            # return (and land every spill's host copy) before reserving,
            # so the free-list sequence (and thus every page id ever
            # issued) matches the synchronous loop
            self._worker.flush()
        resumed: tuple | list = ()
        if self._tier is not None:
            if self._park_order:
                with pl.Span("serve.resume", parked=len(self._park_order)):
                    cache, resumed = self._resume_parked(cache)
            if want and len(self._free_pages) < self._wm_low:
                cache = self._evict_until(self._wm_high, cache,
                                          protect=resumed)
        for i in range(self.batch):
            if self._slots[i] is not None or i in self._parked \
                    or self._qi >= len(queue):
                continue
            r = queue[self._qi]
            if self.paged:
                ok = self._reserve_pages(r, i)
                if not ok and self._tier is not None:
                    # blocked reservation: evict cold slots on demand and
                    # retry once (never past resumed slots — re-parking a
                    # slot that just streamed back would thrash)
                    cache = self._evict_until(
                        max(self._pages_needed(r), self._wm_high), cache,
                        protect=resumed)
                    ok = self._reserve_pages(r, i)
                if not ok:
                    # blocked on pages, not slots: keep decoding; the next
                    # retirement frees pages and re-tries (FCFS, so later
                    # small requests don't starve this one)
                    self.stats["admit_blocked_on_pages"] += 1
                    break
            self._qi += 1
            try:
                cache = self._admit(r, cache, i)
            except Exception:
                if self.paged:
                    # admission failed (e.g. off-ladder prompt): no staged
                    # reservation may leak out of the pool
                    self._release_pages(i)
                    for (_, s, _, _) in self._staged:
                        self._release_pages(s)
                self._staged = []
                raise
            if len(self._staged) >= group_cap:
                cache = self._flush_admissions(cache)
        return self._flush_admissions(cache)

    # ------------------------------------------------------- tiered pool
    def _drain_pending(self, cache):
        """Retire the async pipeline: process every in-flight decode step
        and run all queued bookkeeping. Afterwards `_pos == _devpos` for
        every slot (no speculative step is outstanding) and the free list
        reflects every retirement — the quiescent state parking needs."""
        while self._pending:
            fut, plive, step = self._pending.popleft()
            cache = self._process(fut, plive, step, cache)
        self._worker.flush()
        return cache

    def _park_slot(self, v: int, cache):
        """Evict live slot `v` to the host tier. Returns (parked?, cache);
        False = the host pool can't hold its exclusive pages.

        The caller drained the pipeline, so `_pos[v]` counts every emitted
        token and the device tail holds exactly the slot's raw remainder.
        Exclusively-owned flushed pages are gathered in ONE bucketed jit
        (`paged_gather_slot`, tail rows ride along) and copied host-side on
        the BackgroundWorker — overlapped with whatever decodes next;
        shared pages (refcount > 1) stay device-resident, referenced by the
        parked record. The gather consumed the OLD cache value (XLA buffers
        are immutable), so the spilled device pages return to the free list
        immediately — a later admission can reuse them before the host copy
        lands. Unflushed reserved pages simply roll back; the slot's table
        row and tail zero out, and its batch row leaves the live set."""
        pages = self._slot_pages[v]
        nb = int(self._pos[v]) // kvc.BLOCK
        spill = [(j, pages[j]) for j in range(nb)
                 if self._page_refs[pages[j]] == 1]
        if self._tier.free_pages < len(spill):
            return False, cache
        rec = _ParkedSlot(
            req=self._slots[v], token=int(self._last_tok[v]),
            pos=int(self._pos[v]), horizon_blocks=len(pages),
            shared=self._slot_shared[v], keys=self._slot_keys[v],
            blocks=[("device", pages[j]) for j in range(nb)])
        nbkt = self.ladder.bucket_for(max(len(spill), 1) * kvc.BLOCK) \
            // kvc.BLOCK
        ids = np.full(nbkt, self._n_pages, np.int32)
        ids[:len(spill)] = [p for _, p in spill]
        upd = self._spill(cache, jnp.int32(v), jnp.asarray(ids))
        host_ids = self._tier.alloc(len(spill))
        for (j, _), hid in zip(spill, host_ids):
            rec.blocks[j] = ("host", hid)
        self._worker.submit(functools.partial(
            self._bk_spill, rec, host_ids, upd))
        cache = self._reset(cache, jnp.int32(v))
        for _, p in spill:
            self._page_refs[p] = 0
            self._free_pages.append(p)
            if self._prefix is not None:
                self._prefix.drop_page(p)
        future = pages[nb:]
        self._slot_pages[v] = []
        self._slot_shared[v] = 0
        self._slot_keys[v] = []
        self._release_page_list(future)
        self._parked[v] = rec
        self._park_order.append(v)
        self.stats["pages_spilled"] += len(spill)
        self.stats["slots_parked"] += 1
        return True, cache

    def _bk_spill(self, rec, host_ids, upd):
        """Worker half of a park: pull the gathered pages+tail to the host
        and file them (the flush-before-reserve barrier orders this before
        any read_back)."""
        upd = jax.tree.map(np.asarray, upd)
        self._tier.stage_out(host_ids, upd)
        rec.tails = [{k: seg[k] for k in tiering.TAIL_KEYS} for seg in upd]

    def _resume_parked(self, cache):
        """Stream parked slots back in park order (FIFO — their requests
        are the oldest in the system). Each resume re-reserves the slot's
        worst-case horizon, splices host pages + the saved tail back in ONE
        bucketed `paged_write_slot`, rebuilds the table row, and replays
        token/pos via `_apply_fix`, so the next dispatch continues the
        request bitwise where it parked. Caller flushed the worker, so
        every staged-out byte is already in the host store."""
        resumed = []
        while self._park_order:
            v = self._park_order[0]
            rec = self._parked[v]
            n_host = sum(1 for tier, _ in rec.blocks if tier == "host")
            need = n_host + (rec.horizon_blocks - len(rec.blocks))
            if need > len(self._free_pages):
                break  # strict FIFO: later parked slots wait their turn
            nb = len(rec.blocks)
            nbkt = self.ladder.bucket_for(max(nb, 1) * kvc.BLOCK) // kvc.BLOCK
            page_ids = np.full(nbkt, self._n_pages, np.int32)
            entries, host_ids, slot_pages = [], [], []
            for j, (tier, ref) in enumerate(rec.blocks):
                if tier == "host":
                    p = self._free_pages.pop()
                    assert self._page_refs[p] == 0, p
                    self._page_refs[p] = 1
                    page_ids[j] = p
                    entries.append((j, ref))
                    host_ids.append(ref)
                    slot_pages.append(p)
                else:  # stayed device-resident (shared); ref carried over
                    slot_pages.append(ref)
            for _ in range(rec.horizon_blocks - nb):
                p = self._free_pages.pop()
                self._page_refs[p] = 1
                slot_pages.append(p)
            upd = self._tier.read_back(entries, nbkt)
            upd = [dict(seg, **tails)
                   for seg, tails in zip(upd, rec.tails)]
            table_row = np.zeros(self.sc.max_seq // kvc.BLOCK, np.int32)
            table_row[:nb] = slot_pages[:nb]
            cache = self._restore(cache, upd, jnp.int32(v),
                                  jnp.asarray(page_ids),
                                  jnp.asarray(table_row))
            self._tier.release(host_ids)
            self._slot_pages[v] = slot_pages
            self._slot_shared[v] = rec.shared
            self._slot_keys[v] = rec.keys
            if self._prefix is not None:
                for j, key in enumerate(rec.keys[:nb]):
                    self._prefix.register(key, slot_pages[j])
            self._apply_fix([v], [rec.token], [rec.pos])
            del self._parked[v]
            self._park_order.pop(0)
            resumed.append(v)
            self.stats["pages_restored"] += n_host
            self.stats["slots_resumed"] += 1
            used = self._n_pages - len(self._free_pages)
            self.stats["peak_pages_in_use"] = max(
                self.stats["peak_pages_in_use"], used)
        return cache, resumed

    def _evict_until(self, target_free: int, cache, protect=()):
        """Watermark eviction: park victims until `target_free` device
        pages are free, victims run out, or the host pool fills. Victims
        are live, unparked, unprotected slots, LATEST admission first —
        the oldest requests are closest to retiring on their own, so the
        newest slot's pages are the coldest bet. Each park drains the
        one-step-deep pipeline first (the spill gather must see a
        quiescent row)."""
        tried = set(protect)
        while len(self._free_pages) < target_free:
            victims = [i for i in range(self.batch)
                       if self._slots[i] is not None
                       and i not in self._parked and i not in tried]
            if not victims:
                break
            v = max(victims, key=lambda i: self._slot_seq[i])
            tried.add(v)
            cache = self._drain_pending(cache)
            if self._slots[v] is None:
                continue  # retired while draining — its pages came back free
            with pl.Span("serve.park", slot=v):
                ok, cache = self._park_slot(v, cache)
            if not ok:
                break  # host pool exhausted — stop evicting
        return cache

    def _dispatch(self, cache, live):
        """Dispatch one fused decode step (a `serve.dispatch` span, added to
        stats["decode_s"]); token/pos stay on device. Returns the cache,
        the step's token future and the step's number."""
        step = self.stats["steps"] + 1
        with pl.Span("serve.dispatch", self.stats, ("decode_s",),
                     step=step, live=len(live)) as span:
            args = [self.params, self._tok_dev, cache, self._pos_dev]
            decode = self._decode
            if self.paged:
                # decode-bucket ladder: the attend only reads table entries
                # below a row's flushed watermark (pos//8*8 — the page
                # flushed THIS step is still the raw tail), so the deepest
                # live slot's watermark picks the smallest warmed bucket
                # that covers every row. Retired slots' device positions
                # reset to 0 before the next dispatch, so they never hold
                # the bucket high.
                need = max(((int(self._devpos[i]) // kvc.BLOCK) * kvc.BLOCK
                            for i in live), default=0)
                bucket = self.decode_ladder.bucket_for(need)
                decode = self._decode_fns[bucket]
                self.stats["decode_bucket_tokens"] += bucket
                span.set_metadata(bucket=bucket)
                # hand each flushing row its reserved page; every other row
                # gets an out-of-range id the device scatter drops.
                # `_devpos` mirrors the DEVICE position (which advances on
                # speculative steps the host hasn't processed yet); the
                # length guard drops the flush of a row whose retirement is
                # already in flight.
                fp = np.full(self.batch, self._n_pages, np.int32)
                for i in live:
                    p = int(self._devpos[i])
                    blk = p // kvc.BLOCK
                    if p % kvc.BLOCK == kvc.BLOCK - 1 \
                            and blk < len(self._slot_pages[i]):
                        page = self._slot_pages[i][blk]
                        if self._prefix is not None:
                            # copy-on-write guarantee: decode only ever
                            # flushes PAST the shared prefix, into a page
                            # this slot owns exclusively — a write to a
                            # shared page is structurally impossible,
                            # asserted here
                            assert blk >= self._slot_shared[i] \
                                and self._page_refs[page] == 1, \
                                (i, blk, page, int(self._page_refs[page]))
                        fp[i] = page
                args.append(jnp.asarray(fp))
            if self.sc.temperature > 0.0:
                self.rng, sub = jax.random.split(self.rng)
                args.append(sub)
            tok, pos1, cache = decode(*args)
            self._tok_dev, self._pos_dev = tok, pos1
            self._devpos += 1
            self.stats["steps"] += 1
            self.stats["slot_steps_total"] += self.batch
            self.stats["slot_steps_live"] += len(live)
        return cache, tok, step

    def _process(self, fut, plive, step, cache):
        """Read decode step `step`'s tokens (a `serve.readback` span, added
        to stats["wait_s"] and stats["decode_s"]) and apply its bookkeeping
        (a `serve.process` span).

        `plive` is the (slot, request) snapshot at dispatch time; a slot
        retired (or re-admitted) while the step was in flight is skipped —
        the speculative step only ever touched that slot's own planes, all
        overwritten at the next admission."""
        with pl.Span("serve.readback", self.stats, ("wait_s", "decode_s"),
                     step=step):
            toks = np.asarray(fut)  # the only device->host sync of the loop
        with pl.Span("serve.process", step=step):
            return self._apply_step(toks, plive, cache)

    def _apply_step(self, toks, plive, cache):
        """Bookkeeping of one read decode step: append, retire, re-fix."""
        t_emit = time.perf_counter()
        emitted, retired, fix_i = [], [], []
        for i, r in plive:
            if self._slots[i] is not r:
                continue
            tok = int(toks[i])
            self._nout[i] += 1
            self._pos[i] += 1
            self.stats["tokens_out"] += 1
            emitted.append((r, tok, i))
            if self.paged:
                self._last_tok[i] = tok  # park/resume replays this
            if tok == self.sc.eos_id or self._nout[i] >= r.max_new \
                    or self._pos[i] >= self.sc.max_seq:
                self._slots[i] = None  # retire; slot re-admits next round
                self._pos[i] = 0
                self._nout[i] = 0
                cache = self._reset(cache, jnp.int32(i))
                pages = None
                if self.paged:
                    pages, self._slot_pages[i] = self._slot_pages[i], []
                    self._slot_shared[i] = 0
                    self._slot_keys[i] = []
                retired.append((r, pages))
                fix_i.append(i)
        if emitted:
            self._worker.submit(functools.partial(
                self._bk_step, emitted, retired, t_emit))
        if fix_i:
            self._apply_fix(fix_i, [0] * len(fix_i), [0] * len(fix_i))
            if self.paged and self.paranoid_pool_checks:
                self._worker.flush()
                self.check_page_invariants()
        return cache

    def _run_continuous(self, queue: list[Request]) -> None:
        b = self.batch
        self._slots: list[Request | None] = [None] * b
        self._pos = np.zeros(b, np.int64)      # logical per-slot position
        self._nout = np.zeros(b, np.int64)     # tokens emitted per slot
        self._devpos = np.zeros(b, np.int64)   # device pos mirror (see _dispatch)
        self._last_emit = np.zeros(b)
        self._last_tok = np.zeros(b, np.int64)  # last emitted token per slot
        self._tok_dev = self.device_vec(np.zeros((b,), np.int32))
        self._pos_dev = self.device_vec(np.zeros((b,), np.int32))
        self._staged = []
        self._qi = 0
        cache = self._cache_init(b)
        # async_host: run one step deep — dispatch step t+1 before reading
        # step t's tokens, so the device never idles on host bookkeeping.
        # Slot independence makes the speculation safe: a step dispatched
        # for a slot that retires under it only writes that slot's own
        # tail/table/pages, all reset or overwritten before anything reads
        # them, and its token is discarded in _process.
        depth = 1 if self.sc.async_host else 0
        self._pending = pending = collections.deque()
        self._worker = pl.BackgroundWorker()
        idle_spins, last_state = 0, None
        try:
            while True:
                cache = self._admit_free_slots(queue, cache)
                # parked slots keep their Request in _slots (the slot stays
                # reserved for them) but leave the live set: their batch
                # row decodes garbage that is never read, and their pages
                # are host-side until resume
                live = [(i, r) for i, r in enumerate(self._slots)
                        if r is not None and i not in self._parked]
                if not live and not pending:
                    if self._qi >= len(queue) and not self._parked:
                        break
                    # everything retired at admission (or only parked slots
                    # remain); admit/resume more. Guard the spin: a parked
                    # slot that can never resume would otherwise loop here
                    # forever.
                    state = (self._qi, len(self._parked),
                             len(self._free_pages) if self.paged else 0)
                    idle_spins = idle_spins + 1 if state == last_state else 0
                    last_state = state
                    if idle_spins > 2 * self.batch + 4:
                        raise RuntimeError(
                            "serve loop wedged: no live slots and no "
                            f"progress (parked={sorted(self._parked)}, "
                            f"qi={self._qi}/{len(queue)})")
                    continue
                idle_spins, last_state = 0, None
                if live:
                    self.stats["peak_live_slots"] = max(
                        self.stats["peak_live_slots"], len(live))
                    cache, fut, step = self._dispatch(
                        cache, [i for i, _ in live])
                    pending.append((fut, live, step))
                if len(pending) > depth or (pending and not live):
                    fut, plive, step = pending.popleft()
                    cache = self._process(fut, plive, step, cache)
            # queue drained: record the DATA-DEPENDENT pool footprint next to
            # the analytic one (kv_pool_stats reports both) — variable-length
            # codec families (bitplane) are the reason the two differ.  Raw
            # caches (kv_compress=False) are a plain dict with nothing to
            # measure.
            if hasattr(cache, "segments"):
                self.stats["measured_kv_bytes"] = \
                    kvc.measured_cache_bytes(cache)
        finally:
            worker, self._worker = self._worker, None
            worker.close()

    # ----------------------------------------------------- static scheduler
    def _run_wave(self, wave: list[Request]) -> None:
        """Lock-step wave: right-aligned prompts, one scalar position."""
        assert len(wave) <= self.batch
        # every wave request is live from prefill until it retires
        self.stats["peak_live_slots"] = max(self.stats["peak_live_slots"],
                                            len(wave))
        slots = list(wave) + [
            Request(uid=-1, prompt=np.zeros(kvc.BLOCK, np.int32), max_new=1)
            for _ in range(self.batch - len(wave))
        ]
        plen = max(kvc.BLOCK, max(len(r.prompt) for r in slots))
        prompts = np.zeros((self.batch, plen), np.int32)
        for i, r in enumerate(slots):
            prompts[i, plen - len(r.prompt):] = r.prompt  # right-align

        with pl.Span("serve.admit", self.stats, ("prefill_s",),
                     rows=len(wave), bucket=plen):
            logits, cache = self._prefill(self.params, jnp.asarray(prompts))

        # explicit ordering: sample from prefill -> append/check -> only then
        # decode. If every request finishes on its first token, no decode
        # step runs and no logits are sampled twice.
        token = self._sample(logits[:, -1])
        max_new = max(r.max_new for r in wave)
        done = np.zeros(self.batch, bool)
        for step in range(max_new):
            # the first read waits on the prefill, each later one on the
            # decode step dispatched last
            with pl.Span("serve.readback", self.stats, ("wait_s", "decode_s"),
                         step=self.stats["steps"]):
                tok_np = np.asarray(token)
            for i, r in enumerate(slots):
                if r.uid >= 0 and not r.done:
                    tok = int(tok_np[i])
                    r.out_tokens.append(tok)
                    self.stats["tokens_out"] += 1
                    if tok == self.sc.eos_id or len(r.out_tokens) >= r.max_new:
                        r.done = True
                done[i] = r.done or r.uid < 0
            if done.all():
                break
            if plen + step >= self.sc.max_seq:
                # context exhausted: no slot can write another token — retire
                # the wave truncated (mirrors the continuous pos >= max_seq
                # guard) instead of silently dropping K/V writes
                for r in slots:
                    if r.uid >= 0:
                        r.done = True
                break
            live = int((~done).sum())
            with pl.Span("serve.dispatch", self.stats, ("decode_s",),
                         step=self.stats["steps"] + 1, live=live):
                logits_step, cache = self._decode(self.params, token, cache,
                                                  jnp.int32(plen + step))
                token = self._sample(logits_step)
            self.stats["steps"] += 1
            self.stats["slot_steps_total"] += self.batch
            self.stats["slot_steps_live"] += live
