"""Page pool: admissions refused for want of free pages in the window
(`Engine.stats` admit_blocked_on_pages).  None without a page pool."""
from chipbench.metrics import delta


def read(ctx):
    if ctx["keep"] is None:
        return None
    return float(delta(ctx, "admit_blocked_on_pages"))
