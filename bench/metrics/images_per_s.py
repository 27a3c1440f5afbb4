"""Images whose answers came back within the window, over the window
(host clock)."""


def read(ctx):
    return ctx.completed_rows() / (ctx.t1 - ctx.t0)
