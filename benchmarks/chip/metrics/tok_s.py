"""End to end: output tokens emitted in the window (counted from each
request's served tokens at the window's two edges) per second of window."""


def read(ctx):
    return ctx["tokens"] / ctx["window_s"]
