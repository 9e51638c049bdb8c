"""End to end: 95th percentile of every inter-token gap the engine recorded
in the window (host clock at token readback), in milliseconds."""
import numpy as np


def read(ctx):
    if not ctx["itl_s"]:
        return None
    return 1e3 * float(np.percentile(ctx["itl_s"], 95))
