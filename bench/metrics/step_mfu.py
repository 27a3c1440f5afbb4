"""The whole forward's share of the chips' int8 peak: images answered
times 2 x MACs per image (every layer of the configuration), over the
time, the chips and the peak (%).  Taken over the part of the window
before tracing started, so the tracer's own cost stays out of it."""


def read(ctx):
    if not ctx.peaks:
        return None
    a, b = ctx.untraced()
    ops = ctx.completed_rows(a, b) * ctx.ops_per_image()
    return 100.0 * ops / ((b - a) * ctx.chips * ctx.peaks["int8_ops_per_s"])
