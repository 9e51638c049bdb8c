"""Model step: bytes the window's decode steps had to read (every matmul
weight once per step, plus the K/V of each decode token's own context:
its occupied pool pages, or its raw positions) per second of the window,
as a share of the cell's chips' peak HBM bandwidth."""
from chipbench import flops
from chipbench.metrics import delta


def read(ctx):
    steps = delta(ctx, "steps")
    if steps <= 0:
        return None
    need = flops.weight_bytes(ctx["block"], ctx["dims"]) * steps \
        + ctx["work"]["kv_bytes"]
    peak = ctx["chips"] * ctx["peaks"]["hbm_bytes_s"]
    return 100.0 * need / ctx["window_s"] / peak
