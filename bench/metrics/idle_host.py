"""Share of the traced window in which no operation ran on the device
while the host serving path held it back: a full garbage collection was
open, or the cell's batch worker was anywhere but ``collect`` (blocked
waiting for a batch's first request).  ``device_idle`` is this plus the
idle time the worker spent in ``collect`` with no collection open.

Read from the program tracer's raw spans laid over the profiler trace
(``bench.progtrace``); ``describe`` prints the idle time by worker
state, the ten longest idle gaps named by the state that held most of
each, and the checks on the two clocks."""
from bench import progtrace


def read(ctx):
    att = progtrace.attribute(ctx)
    if att is None or att["window_s"] <= 0:
        return None
    split = att["split_s"]
    return (att["idle_s"] - split["collect"]) / att["window_s"]


def describe(ctx):
    att = progtrace.attribute(ctx)
    if att is None:
        return None
    win, split = att["window_s"], att["split_s"]
    inside, n_mod = att["modules"]
    off, how, err = att["offset"]
    states = ", ".join(f"{s} {v!r}" for s, v in split.items())
    gaps = ", ".join(f"{s!r} s {state}" for s, state in att["gaps"])
    share = inside / n_mod if n_mod else float("nan")
    return (f"device idle by worker state (s of a {win!r} s window, "
            f"{att['idle_s']!r} s idle): {states}; idle in collect "
            f"{split['collect'] / win!r} of the window, between spans "
            f"{split['between spans'] / max(att['idle_s'], 1e-12)!r} of the "
            f"idle time; longest idle gaps: {gaps}; XLA Modules starting "
            f"inside a dispatch span: {inside} of {n_mod} ({share!r}); "
            f"jax-compile spans in the window: {att['compiles']}; program "
            f"clock offset {off!r} ns ({how}, +/- {err!r} ns)")
