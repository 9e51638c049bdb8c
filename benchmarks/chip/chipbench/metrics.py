"""Per-layer metric readers, one file each under `metrics/`, found by name.

A reader is `metrics/<name>.py` defining `read(ctx) -> float | None`.  It
returns None when the run holds nothing for it to read (no trace, no such
kernel in the trace); the harness then leaves the metric out of the line.

`ctx` keys:
  window_s          host seconds of the measured window
  stats0, stats1    `Engine.stats` at the window's two edges
  work              `flops.window_work` of the tokens served in the window
  block, dims       the configuration's block module and its `model_dims`
  keep              the codec's keep, or None where the cell keeps raw K/V
  chips             chips the cell serves on (a share of a peak is of
                    chips x the one-chip peak)
  peaks             `peaks.peaks_for(device_kind)`, of one chip
  trace             None, or {"planes": [trace.Plane], "t0", "t1"
                    (the traced window on the trace's clock, ns),
                    "window_s"}
"""
from __future__ import annotations

import importlib.util

from chipbench.spec import METRIC_DIR


def load_reader(name: str):
    path = METRIC_DIR / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader {path} for metric {name!r}")
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def delta(ctx, key: str) -> float:
    return ctx["stats1"][key] - ctx["stats0"][key]


def kernel_seconds(ctx, match) -> float | None:
    """Device seconds in which an op event that `match(op, hlo_text)` ran,
    averaged over the traced chips; None without a trace or a match."""
    tr = ctx["trace"]
    if tr is None:
        return None
    from chipbench import trace as trace_lib

    per_chip = []
    for p in tr["planes"]:
        hits = {op for op, text in p.hlo.items() if match(op, text)}
        evs = [e for e in p.ops if e[0] in hits]
        per_chip.append(trace_lib.busy_ns(evs, tr["t0"], tr["t1"]))
    total = sum(per_chip) / len(per_chip) if per_chip else 0.0
    return total * 1e-9 if total > 0 else None
