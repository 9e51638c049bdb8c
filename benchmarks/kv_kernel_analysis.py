"""Analytic memory-term comparison for decode: raw cache vs XLA-compressed
vs the fused Pallas decompress-attend kernel — every applicable (arch, shape).

The dry-run can only measure what XLA materializes; the fused kernel's
traffic is determined by its BlockSpecs (packed int8 tiles + scales stream
HBM->VMEM once; decompressed K/V never exist in HBM), so its memory term is
computed here from shapes and the same v5e constants, per (arch x decode
shape) on the single-pod mesh. VMEM residency per grid step is checked
against the 16 MB budget — a kernel that doesn't fit is reported, not
assumed.

    PYTHONPATH=src python -m benchmarks.kv_kernel_analysis
"""
from __future__ import annotations

import json
import os

from repro.configs.base import ARCH_IDS, SHAPES, get_config
from repro.roofline.analysis import TARGET_KIND, chip_peaks, hbm_bandwidth_row

HBM_BW = chip_peaks(TARGET_KIND).hbm_bw

BLOCK = 8
VMEM_BUDGET = 16 * 2**20
CHIPS = 256  # single-pod 16x16


def decode_cell(cfg, shape_name: str, keep: int = 4, tile_s: int = 512):
    seq, batch, kind = SHAPES[shape_name]
    if kind != "decode":
        return None
    ok, why = cfg.shape_supported(shape_name)
    if not ok:
        return {"skip": why}
    if cfg.attn_type != "gqa" or cfg.family not in ("dense", "moe", "vlm", "hybrid"):
        return {"skip": f"KVCompress inapplicable ({cfg.attn_type}/{cfg.family})"}
    hd, hkv, L = cfg.resolved_head_dim, cfg.n_kv_heads, cfg.n_layers
    if hd % BLOCK:
        return {"skip": f"head_dim {hd} not 8-tileable"}
    if cfg.family == "hybrid":
        L = cfg.n_layers // max(cfg.attn_every, 1)  # shared-attn caches only

    # per-device partitioning: batch over data(16), heads over model(16)
    # when divisible, else sequence over model
    b_loc = max(batch // 16, 1)
    if hkv % 16 == 0 and hkv >= 16:
        hkv_loc, s_loc = hkv // 16, seq
    else:
        hkv_loc, s_loc = hkv, seq // 16

    # raw decode: read k+v bf16 once per layer
    raw = L * b_loc * s_loc * hkv_loc * hd * 2 * 2

    # fused kernel: packed int8 + f32 scales once per layer + tail +
    # amortized flush (packed-store DUS every 8 steps over the seq shard)
    per_tile = keep * keep + 4
    packed = L * b_loc * (s_loc // BLOCK) * hkv_loc * (hd // BLOCK) * per_tile * 2
    tail = L * b_loc * BLOCK * hkv_loc * hd * 2 * 2 * 2        # rw of raw tail
    flush = packed / BLOCK                                      # amortized rewrite
    fused = packed + tail + flush

    # VMEM per grid step: packed k/v tiles + scales + decompressed tiles f32
    ts8 = tile_s // BLOCK
    vmem = 2 * (ts8 * hkv_loc * (hd // BLOCK) * per_tile) \
        + 2 * (tile_s * hkv_loc * hd * 4) \
        + 2 * (cfg.n_heads * hd * 4)
    return {
        "raw_ms": raw / HBM_BW * 1e3,
        "xla_compressed_note": "~2x raw (unfused decompress, measured on yi)",
        "fused_ms": fused / HBM_BW * 1e3,
        "speedup": raw / fused,
        "vmem_ok": vmem <= VMEM_BUDGET,
        "vmem_mb": vmem / 2**20,
        "raw_gb_dev": raw / 1e9,
        "fused_gb_dev": fused / 1e9,
    }


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def attend_paged_cell(cfg, shape_name: str, keep: int = 4,
                      occupancy: float = 0.5, pages_per_tile: int = 8,
                      pool_tokens: int | None = None):
    """Step cost and achieved HBM bandwidth for the multi-page tiled
    `attend_paged` vs its single-page-per-grid-step predecessor.

    Both kernels DMA one table entry's page per gather lane, so bytes
    scale with the blocks their GRID covers — the old kernel's grid was
    sized to pool CAPACITY (every step a tiny one-page tile: 8/128 of the
    MXU contraction, and one un-hideable DMA issue per step), the new one
    to the decode-ladder BUCKET covering the occupied context, fetching G
    pages per step into one (G*8, hd) MXU-shaped tile.  `occupancy` is the
    live fraction of `pool_tokens` (default: the shape's seq) — at low
    occupancy in a large pool the old grid is pure latency
    (`step_cost_vs_singlepage_grid` is the acceptance ratio); at full
    occupancy the G-wide tile turns the same bytes into fewer, larger DMAs
    (`achieved_bw_gbs` > `achieved_bw_singlepage_gbs`).  A dense-layout
    kernel streams every slot's full capacity allocation regardless —
    `bw_saving_vs_dense` stays the measured-bytes half of the paged claim.
    """
    seq, batch, kind = SHAPES[shape_name]
    if kind != "decode":
        return None
    ok, why = cfg.shape_supported(shape_name)
    if not ok:
        return {"skip": why}
    if cfg.attn_type != "gqa" or cfg.family not in ("dense", "moe", "vlm", "hybrid"):
        return {"skip": f"KVCompress inapplicable ({cfg.attn_type}/{cfg.family})"}
    hd, hkv, L = cfg.resolved_head_dim, cfg.n_kv_heads, cfg.n_layers
    if hd % BLOCK:
        return {"skip": f"head_dim {hd} not 8-tileable"}
    if cfg.family == "hybrid":
        L = cfg.n_layers // max(cfg.attn_every, 1)
    b_loc = max(batch // 16, 1)
    if hkv % 16 == 0 and hkv >= 16:
        hkv_loc, s_loc, nq_loc = hkv // 16, seq, cfg.n_heads // 16
    else:
        hkv_loc, s_loc, nq_loc = hkv, seq // 16, cfg.n_heads

    per_tile = keep * keep + 4           # int8 corner + f32 scale, per 8x8
    cap_blocks = (pool_tokens if pool_tokens else s_loc) // BLOCK
    mapped = max(int(cap_blocks * occupancy), 1)
    # one page's stream, per layer per slot: packed K + V planes
    page_bytes = hkv_loc * (hd // BLOCK) * per_tile * 2
    tails = L * b_loc * BLOCK * hkv_loc * hd * 2 * 2   # raw bf16 k+v tails
    qo = L * b_loc * nq_loc * hd * 2 * 2               # q in + attn out

    def model(grid_blocks: int, g: int) -> dict:
        """One decode step with a grid over `grid_blocks` table entries,
        gathering g pages per step."""
        while grid_blocks % g:            # kernel's fit_tile: divisor of grid
            g -= 1
        grid_steps = L * b_loc * hkv_loc * (grid_blocks // g)
        packed = L * b_loc * grid_blocks * page_bytes
        table = L * b_loc * grid_blocks * 4            # s32 block-table walk
        bytes_step = packed + table + tails + qo
        # QK^T + AV over the tiles pl.when actually runs: whole g-page tiles
        # up to the watermark, plus the fused raw tail
        tiles = -(-min(mapped, grid_blocks) // g)
        flops = 4.0 * L * b_loc * nq_loc * hd * (tiles * g + 1) * BLOCK
        row = hbm_bandwidth_row(
            bytes_step, flops, grid_steps=grid_steps,
            mxu_efficiency=min(1.0, g * BLOCK / 128), device_kind=TARGET_KIND)
        row["grid_blocks"] = grid_blocks
        row["g"] = g
        return row

    # old kernel: grid = pool capacity, one page per step; new kernel:
    # grid = the ladder bucket covering the occupied context, G per step
    old = model(cap_blocks, 1)
    bucket_blocks = min(_next_pow2(mapped), cap_blocks)
    new = model(bucket_blocks, pages_per_tile)

    # VMEM per grid step (double-buffered inputs + scratch + out), G tile
    rep = max(nq_loc // hkv_loc, 1)
    g = new["g"]
    vmem = 2 * (2 * g * (hd // BLOCK) * per_tile                # packed+scale
                + 2 * BLOCK * hd * 4                            # raw tails
                + rep * hd * 4 + keep * BLOCK * 4) \
        + 2 * g * BLOCK * hd * 4 * 2 \
        + rep * hd * 4 * 2 + rep * 2 * 4
    dense_bytes = L * b_loc * cap_blocks * page_bytes + tails + qo
    row = {
        "occupancy": occupancy,
        "pool_tokens": cap_blocks * BLOCK,
        "mapped_pages_per_slot": mapped,
        "bucket_tokens": bucket_blocks * BLOCK,
        "pages_per_tile": g,
        "page_stream_bytes": page_bytes,
        "bw_saving_vs_dense": dense_bytes / new["bytes_per_step"],
        "step_cost_vs_singlepage_grid": old["step_bound_s"] / new["step_bound_s"],
        "achieved_bw_singlepage_gbs": old["achieved_bw_gbs"],
        "vmem_ok": vmem <= VMEM_BUDGET,
        "vmem_mb": vmem / 2**20,
    }
    row.update({k: v for k, v in new.items() if k not in ("grid_blocks", "g")})
    return row


def main(quick: bool = False):
    rows = {}
    print(f"{'arch':24s} {'shape':12s} {'raw ms':>8s} {'fused ms':>9s} "
          f"{'speedup':>8s} {'VMEM MB':>8s}")
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in ("decode_32k", "long_500k"):
            r = decode_cell(cfg, shape)
            if r is None:
                continue
            rows[f"{arch}/{shape}"] = r
            if "skip" in r:
                print(f"{arch:24s} {shape:12s} skip: {r['skip']}")
                continue
            print(f"{arch:24s} {shape:12s} {r['raw_ms']:8.2f} {r['fused_ms']:9.3f} "
                  f"{r['speedup']:7.1f}x {r['vmem_mb']:8.2f}{'' if r['vmem_ok'] else '  !VMEM'}")
            assert r["vmem_ok"], (arch, shape, r["vmem_mb"])
            assert r["speedup"] > 4.0
            # three paged operating points: serving steady state (half the
            # pool mapped), full occupancy (peak-bandwidth claim), and a
            # short context in a big pool (ladder + latency claim)
            paged_cells = {
                "attend_paged": attend_paged_cell(cfg, shape),
                "attend_paged_full": attend_paged_cell(cfg, shape,
                                                       occupancy=1.0),
                "attend_paged_short": attend_paged_cell(
                    cfg, shape, occupancy=256 / 4096, pool_tokens=4096),
            }
            for name, p in paged_cells.items():
                if not p or "skip" in p:
                    continue
                rows[f"{arch}/{shape}/{name}"] = p
                print(f"{'':24s} ^{name[7:]:11s} "
                      f"{p['achieved_bw_gbs']:8.1f}/{p['peak_bw_gbs']:.0f} GB/s "
                      f"(util {p['hbm_utilization']:.2f}, "
                      f"bucket {p['bucket_tokens']} G={p['pages_per_tile']}, "
                      f"{p['step_cost_vs_singlepage_grid']:.1f}x vs 1-page, "
                      f"{p['bw_saving_vs_dense']:.1f}x fewer bytes vs dense)")
                assert 0.0 < p["hbm_utilization"] <= 1.0, p
                if p["occupancy"] < 1.0:   # the byte saving IS occupancy:
                    assert p["bw_saving_vs_dense"] > 1.0, p
                else:                      # full pool = dense bytes + table
                    assert p["bw_saving_vs_dense"] > 0.98, p
                assert p["vmem_ok"], (arch, shape, name, p["vmem_mb"])
            full = paged_cells["attend_paged_full"]
            if full and "skip" not in full:
                # acceptance: >= 2x cheaper step at 256 live tokens in a 4k
                # pool; strictly higher achieved bandwidth at full occupancy
                short = paged_cells["attend_paged_short"]
                assert short["step_cost_vs_singlepage_grid"] >= 2.0, short
                assert full["achieved_bw_gbs"] > \
                    full["achieved_bw_singlepage_gbs"], full
    art = os.path.join(os.path.dirname(__file__), "artifacts")
    os.makedirs(art, exist_ok=True)
    with open(os.path.join(art, "kv_kernel_analysis.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    main()
