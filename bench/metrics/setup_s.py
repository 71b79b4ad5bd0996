"""Seconds from process start to the first timed request: data, index
build, placement, compilation or cache load, warm-up."""


def read(ctx):
    return ctx.setup_s
