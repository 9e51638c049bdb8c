"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Three terms per (arch x shape x mesh), all in SECONDS per step, derived from
the post-SPMD per-device module and the target chip's peaks (`CHIP_PEAKS`,
keyed by `jax.Device.device_kind`; v5e: 197 TF/s bf16, 819 GB/s HBM,
50 GB/s per ICI link):

  compute    = HLO_FLOPs / peak FLOP/s
  memory     = HLO_bytes / HBM bandwidth
  collective = wire_bytes / ICI link bandwidth

Sources: `compiled.cost_analysis()` supplies per-device FLOPs and bytes
(the compiled module is the per-device SPMD program). Collective bytes are
NOT in cost_analysis; we parse `compiled.as_text()` and charge each op the
ring-algorithm wire cost per device:

  all-reduce       2 x operand bytes      (reduce-scatter + all-gather ring)
  all-gather       result - operand       (receives everyone else's shard)
  reduce-scatter   operand - result
  all-to-all       operand bytes          (sends all but its own slice)
  collective-permute  operand bytes

The dominant term approximates step time on hardware that overlaps the other
two perfectly; the roofline fraction we report is dominant / sum (how close
a perfect-overlap schedule would run to the single-resource bound).

MODEL_FLOPS accounting: 6*N*D for training (fwd 2ND + bwd 4ND), 2*N*D for
prefill, 2*N_active per generated token for decode — divided by chip count
to compare against the per-device HLO FLOPs; the ratio exposes remat
recompute and padding waste.
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field


@dataclass(frozen=True)
class ChipPeaks:
    flops: float      # bf16 FLOP/s per chip
    hbm_bw: float     # HBM bytes/s per chip
    ici_bw: float     # ICI bytes/s per link
    source: str


# keyed by jax.Device.device_kind (a v5e reports "TPU v5 lite"); a device
# missing here is an error, never a default — a roofline share against the
# wrong chip's peaks is meaningless
CHIP_PEAKS = {"TPU v5 lite": ChipPeaks(
    flops=197e12, hbm_bw=819e9, ici_bw=50e9,
    source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
           "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip (4 links)")}
# the chip the dry-run and kernel models target (its `device_kind`)
TARGET_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of the chip `device_kind` names; KeyError for any other."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(CHIP_PEAKS)}") from None


# Per-grid-step DMA latency a double-buffered pallas pipeline cannot hide
# when each step's tiles are tiny (a paged-attend page is ~100s of bytes):
# issue + descriptor + HBM round-trip tail, ~0.5us. A kernel whose grid has
# many small steps is latency-bound long before it is bandwidth-bound —
# exactly what multi-page (G) tiling amortizes.
PAGE_DMA_LATENCY_S = 0.5e-6

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
# e.g. "%ag = bf16[4,128]{1,0} all-gather(bf16[1,128]{1,0} %x), ..."
_OP_RE = re.compile(
    r"=\s+(?P<result>\([^)]*\)|\S+)\s+"
    r"(?P<kind>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\((?P<operands>.*?)\)(?:,|\s|$)"
)


def _shape_bytes(typestr: str) -> int:
    """Total bytes of an HLO type string (handles tuples)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(typestr):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> dict[str, dict[str, float]]:
    """Per-kind {count, operand_bytes, result_bytes, wire_bytes} from HLO."""
    out = {k: {"count": 0, "operand_bytes": 0.0, "result_bytes": 0.0,
               "wire_bytes": 0.0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if not any(k in line for k in _COLLECTIVES):
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group("kind")
        res = _shape_bytes(m.group("result"))
        ops = _shape_bytes(m.group("operands"))
        if kind == "all-reduce":
            wire = 2.0 * ops
        elif kind == "all-gather":
            wire = max(res - ops, 0)
        elif kind == "reduce-scatter":
            wire = max(ops - res, 0)
        else:  # all-to-all, collective-permute
            wire = float(ops)
        d = out[kind]
        d["count"] += 1
        d["operand_bytes"] += ops
        d["result_bytes"] += res
        d["wire_bytes"] += wire
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    device_kind: str                 # key into CHIP_PEAKS
    hlo_flops: float                 # per device
    hlo_bytes: float                 # per device
    wire_bytes: float                # per device
    model_flops_global: float        # analytic useful FLOPs (whole step)
    collectives: dict = field(default_factory=dict)
    memory_stats: dict = field(default_factory=dict)

    @property
    def peaks(self) -> ChipPeaks:
        return chip_peaks(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / self.peaks.flops

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.wire_bytes / self.peaks.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """dominant / sum: 1.0 = one resource fully hides the others."""
        s = self.compute_s + self.memory_s + self.collective_s
        return self.bound_s / s if s else 0.0

    @property
    def useful_flop_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (per device); <1 => remat/padding waste."""
        per_dev = self.model_flops_global / max(self.chips, 1)
        return per_dev / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model FLOPs utilization IF the step ran at bound_s."""
        per_dev = self.model_flops_global / max(self.chips, 1)
        return per_dev / (self.bound_s * self.peaks.flops) \
            if self.bound_s else 0.0

    def to_dict(self) -> dict:
        d = asdict(self)
        d.update(
            compute_s=self.compute_s, memory_s=self.memory_s,
            collective_s=self.collective_s, dominant=self.dominant,
            roofline_fraction=self.roofline_fraction,
            useful_flop_ratio=self.useful_flop_ratio,
            mfu_bound=self.mfu_bound,
        )
        return d


def hbm_bandwidth_row(bytes_per_step: float, compute_flops: float = 0.0,
                      grid_steps: float = 0.0,
                      mxu_efficiency: float = 1.0, *,
                      device_kind: str) -> dict:
    """Achieved vs peak HBM bandwidth for one (memory-streaming) step.

    `bytes_per_step` is what the kernel ACTUALLY streams (for attend_paged:
    only pages mapped in the block table, their scales, the raw tails, and
    the table itself — never unmapped pool capacity). The step-time bound is
    the roofline max of the memory, compute, and grid-latency terms;
    achieved bandwidth is the useful stream over that bound, so
    `hbm_utilization` < 1 exactly when the step leaves the memory system
    idle waiting on compute or on per-tile DMA issue.

    `grid_steps` charges PAGE_DMA_LATENCY_S per pallas grid step — the
    un-hideable tail of a tiny-tile double-buffered pipeline (0 = dense
    streaming kernel, latency folded into bandwidth). `mxu_efficiency`
    derates the peak FLOP/s for tiles narrower than the 128-lane
    contraction (a one-page tile runs 8/128 of the MXU).  Peaks come from
    `chip_peaks(device_kind)`.
    """
    pk = chip_peaks(device_kind)
    mem_s = bytes_per_step / pk.hbm_bw
    comp_s = compute_flops / (pk.flops * max(mxu_efficiency, 1e-9))
    lat_s = grid_steps * PAGE_DMA_LATENCY_S
    step_s = max(mem_s, comp_s, lat_s)
    achieved = bytes_per_step / step_s if step_s else 0.0
    return {
        "bytes_per_step": float(bytes_per_step),
        "step_bound_s": step_s,
        "memory_s": mem_s,
        "compute_s": comp_s,
        "grid_latency_s": lat_s,
        "grid_steps": float(grid_steps),
        "achieved_bw_gbs": achieved / 1e9,
        "peak_bw_gbs": pk.hbm_bw / 1e9,
        "hbm_utilization": achieved / pk.hbm_bw,
    }


def model_flops(cfg, shape_name: str, n_layers_factor: float = 1.0) -> float:
    """Analytic useful FLOPs per step: 6ND train / 2ND prefill / 2ND' decode."""
    from repro.configs.base import SHAPES

    seq, batch, kind = SHAPES[shape_name]
    counts = cfg.param_counts()
    n_active = counts["active"]
    if cfg.is_encoder_decoder:
        seq = min(seq, cfg.max_seq_len or seq) + cfg.encoder_seq_len
    if kind == "train":
        return 6.0 * n_active * batch * seq
    if kind == "prefill":
        return 2.0 * n_active * batch * seq
    # decode: one token per sequence in the batch + attention re-read cost
    # (attention flops ~ 2 * 2 * S * d_model * n_layers, folded into n_active
    #  only approximately; report pure 2*N_active*B as the conventional bound)
    return 2.0 * n_active * batch


def from_compiled(arch: str, shape: str, mesh_name: str, chips: int,
                  compiled, cfg, device_kind: str = TARGET_KIND) -> Roofline:
    """Derive the three terms from the compiled per-device module.

    FLOPs/bytes/wire come from the trip-count-aware HLO analyzer
    (roofline/hlo.py) — XLA's own cost_analysis counts while bodies once and
    undercounts scan-over-layers models by ~L x; its raw values are kept in
    the record as `xla_cost_analysis` for cross-reference.
    """
    from repro.roofline import hlo as hlo_lib

    st = hlo_lib.analyze(compiled.as_text())
    ca = compiled.cost_analysis() or {}
    try:
        ms = compiled.memory_analysis()
        mem = {
            "argument_bytes": ms.argument_size_in_bytes,
            "output_bytes": ms.output_size_in_bytes,
            "temp_bytes": ms.temp_size_in_bytes,
            "alias_bytes": ms.alias_size_in_bytes,
        }
    except Exception:
        mem = {}
    mem["xla_cost_analysis"] = {
        "flops_body_once": float(ca.get("flops", 0.0)),
        "bytes_body_once": float(ca.get("bytes accessed", 0.0)),
    }
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        device_kind=device_kind, hlo_flops=st.flops, hlo_bytes=st.bytes, wire_bytes=st.wire,
        model_flops_global=model_flops(cfg, shape),
        collectives=st.coll, memory_stats=mem,
    )


def format_row(r: Roofline) -> str:
    return (
        f"{r.arch:24s} {r.shape:12s} {r.mesh:10s} "
        f"comp {r.compute_s*1e3:9.2f}ms  mem {r.memory_s*1e3:9.2f}ms  "
        f"coll {r.collective_s*1e3:9.2f}ms  dom={r.dominant:10s} "
        f"frac={r.roofline_fraction:.2f} useful={r.useful_flop_ratio:.2f}"
    )
