"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-op intervals) / window, from the profiler
trace, averaged over the chips used."""


def read(ctx):
    dev = ctx.device
    if dev is None or dev["window_s"] <= 0:
        return None
    return 1.0 - dev["busy_s"] / dev["window_s"]
