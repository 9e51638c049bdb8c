"""Attend kernel (`kernels/fused_attend` attend_paged): least time for the
window's decode attention on the cell's chips -- the larger of its
operations over their peak FLOP/s and the pool bytes it needs (each
token's occupied pages) over their peak bandwidth -- over the device time
of the kernel's events, averaged over the chips.  None without a page
pool."""
from chipbench.kernels import is_attend_paged
from chipbench.metrics import kernel_seconds


def read(ctx):
    t = kernel_seconds(ctx, is_attend_paged)
    work = ctx["work"]
    if t is None or ctx["keep"] is None or work["kv_bytes"] <= 0:
        return None
    pk, chips = ctx["peaks"], ctx["chips"]
    least = max(work["attn_flops"] / (chips * pk["bf16_flops"]),
                work["kv_bytes"] / (chips * pk["hbm_bytes_s"]))
    return 100.0 * least / t
