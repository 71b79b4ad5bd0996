"""Wall seconds of the program's index build (``build_two_level``)
during set-up; None for a kind that builds no index."""


def read(ctx):
    if ctx.config["index"]["kind"] == "brute":
        return None
    return ctx.build_s
