"""Admission (`prefill_compressed_paged` via the engine's traced_prefill
jit): device time of the admission programs as a share of the traced
window."""
from chipbench import trace as trace_lib

NAME = "traced_prefill"


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    per_chip = [trace_lib.busy_ns([e for e in p.modules if NAME in e[0]],
                                  tr["t0"], tr["t1"]) for p in tr["planes"]]
    busy = sum(per_chip) / max(len(per_chip), 1)
    if busy <= 0:
        return None
    return 100.0 * busy * 1e-9 / tr["window_s"]
