"""Scheduler: share of decode slot-steps in the window that served a live
request (`Engine.stats` slot_steps_live / slot_steps_total)."""
from chipbench.metrics import delta


def read(ctx):
    total = delta(ctx, "slot_steps_total")
    if total <= 0:
        return None
    return 100.0 * delta(ctx, "slot_steps_live") / total
