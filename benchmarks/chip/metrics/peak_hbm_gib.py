"""End to end: peak HBM of the chip in GiB -- the allocator's peak of
buffers in use (weights, the pool, live arrays) plus its peak of memory
reserved for programs' temporaries (the largest warmed admission)."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 2 ** 30
