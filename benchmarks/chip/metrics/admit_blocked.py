"""Page pool: admissions refused for want of free pages in the window
(`Engine.stats` admit_blocked_on_pages)."""
from chipbench.metrics import delta


def read(ctx):
    return float(delta(ctx, "admit_blocked_on_pages"))
