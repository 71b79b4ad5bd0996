"""Requests answered inside the window over the window's seconds."""


def read(ctx):
    if ctx.traffic["loop"] != "closed":
        return None
    return ctx.window.answered_in_window() / ctx.window.seconds
