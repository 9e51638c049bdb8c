"""Attend kernel (`kernels/fused_attend` attend_paged): least time for the
window's decode attention -- the larger of its operations over peak FLOP/s
and the pool bytes it needs (each token's occupied pages) over peak
bandwidth -- over the summed device time of the kernel's events."""
from chipbench.kernels import is_attend_paged
from chipbench.metrics import kernel_seconds


def read(ctx):
    t = kernel_seconds(ctx, is_attend_paged)
    work = ctx["work"]
    if t is None or work["kv_bytes"] <= 0:
        return None
    pk = ctx["peaks"]
    least = max(work["attn_flops"] / pk["bf16_flops"],
                work["kv_bytes"] / pk["hbm_bytes_s"])
    return 100.0 * least / t
