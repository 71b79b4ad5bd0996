"""Median, over answered requests, of the router's time outside the
serving cell (ms): from the ``route`` span's start to the request's
``queue`` span's start (admission, submit), plus from the end of the
``dispatch`` that served it to the ``route`` span's end (delivery and
waking the client thread).  The request joins its dispatch by the cell,
the collection's sequence number ``seq`` and the option group its queue
span carries."""
import numpy as np

from bench import progtrace


def read(ctx):
    out = progtrace.route_outside_ms(ctx)
    return float(np.median(out)) if out else None


def describe(ctx):
    out = progtrace.route_outside_ms(ctx)
    if not out:
        return None
    p = [float(v) for v in np.percentile(out, [50, 90, 99])]
    return (f"route outside the cell: {len(out)} requests joined, p50 "
            f"{p[0]!r} ms, p90 {p[1]!r} ms, p99 {p[2]!r} ms")
