"""Model step: model operations of the window's tokens (prompts admitted in
the window and decode tokens, `flops.window_work`) per second of the
window, as a share of the cell's chips' peak bf16 rate."""


def read(ctx):
    flops = ctx["work"]["model_flops"]
    if flops <= 0:
        return None
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops"]
    return 100.0 * flops / ctx["window_s"] / peak
