"""Device: share of the traced window in which no operation ran on the chip
(1 - union of the `XLA Ops` intervals / window), averaged over chips."""
from chipbench import trace as trace_lib


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["planes"]:
        return None
    busy = [trace_lib.busy_ns(p.ops, tr["t0"], tr["t1"]) for p in tr["planes"]]
    window_ns = tr["t1"] - tr["t0"]
    return 100.0 * (1.0 - sum(busy) / len(busy) / window_ns)
