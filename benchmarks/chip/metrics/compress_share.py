"""Flush codec kernel (`kernels/fused_compress`): device time of its compress
events (the decode-path flush and the admission's bulk compress) as a share
of the traced window."""
from chipbench.kernels import is_compress
from chipbench.metrics import kernel_seconds


def read(ctx):
    t = kernel_seconds(ctx, is_compress)
    if t is None:
        return None
    return 100.0 * t / ctx["trace"]["window_s"]
