"""Model step: bytes the window's decode steps had to read (every matmul
weight once per step, plus the occupied pool pages of each decode token's
own context) per second of the window, as a share of peak HBM bandwidth."""
from chipbench import flops
from chipbench.metrics import delta


def read(ctx):
    steps = delta(ctx, "steps")
    if steps <= 0:
        return None
    need = flops.weight_bytes(ctx["dims"]) * steps + ctx["work"]["kv_bytes"]
    return 100.0 * need / ctx["window_s"] / ctx["peaks"]["hbm_bytes_s"]
