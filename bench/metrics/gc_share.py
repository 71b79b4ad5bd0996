"""Share of the traced window spent inside full (generation-2) garbage
collections: the union of the program's ``gc`` spans, cut to the
window, over the window.  A full collection holds the interpreter lock,
so every host thread of the serving path stalls for its length."""
from bench import progtrace


def read(ctx):
    att = progtrace.attribute(ctx)
    if att is None or att["window_s"] <= 0:
        return None
    return att["gc_s"] / att["window_s"]


def describe(ctx):
    att = progtrace.attribute(ctx)
    if att is None:
        return None
    return (f"full collections in the window: {att['gc_n']}, "
            f"{att['gc_s']!r} s of {att['window_s']!r} s")
