"""99th percentile of each request's wait in its serving cell, from
enqueue to the start of its batch's dispatch (ms).

Read from the program tracer's raw spans: a cell's worker records the
``queue`` span of every request of a batch (enqueue to the batch's first
dequeue) and then that batch's ``batch`` span (first dequeue to
dispatch), on its own thread, so a request waits its ``queue`` plus the
next ``batch`` span of the same thread."""
from bench.traffic import percentile


def read(ctx):
    if not ctx.spans:
        return None
    pending: dict = {}
    waits = []
    for ev in ctx.spans:
        if ev.get("ph") != "X":
            continue
        if ev["name"] == "queue":
            pending.setdefault(ev["tid"], []).append(ev["dur"])
        elif ev["name"] == "batch":
            waits.extend(q + ev["dur"] for q in pending.pop(ev["tid"], []))
    if not waits:
        return None
    return percentile([w / 1e3 for w in waits], 99)
