"""End to end: process start until serving starts -- weights made, engine
built and every shape of the cell compiled or loaded from the cache."""


def read(ctx):
    return ctx["setup_s"]
