"""Scheduler host loop: share of the window in which the serve thread was
not blocked on the device -- 100 x (1 - the window's `Engine.stats`
wait_s, the summed `serve.readback` spans, over window_s).  None where the
program keeps no such counter."""
from chipbench.metrics import delta


def read(ctx):
    if "wait_s" not in ctx["stats0"]:
        return None
    return 100.0 * (1.0 - delta(ctx, "wait_s") / ctx["window_s"])
