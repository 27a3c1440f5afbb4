"""Set-up: process start to the first timed request (host clock):
import, compile or cache load, weights, images and warm-up."""


def read(ctx):
    return ctx.setup_s
