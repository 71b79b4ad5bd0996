"""Share of the roofline reached by the search program (%): the least
time the chip needs for the work of the traced window's dispatches, over
the device time of every program that ran in the window.

The work is counted from the configuration's shapes alone, so it reads
the same whatever implements the search:

* the centroid scan: the K x d centroid table read once per dispatch,
  2 * d flops per query and centroid;
* the probed buckets: each query reads the rows of its ``nprobe``
  buckets once — ``n / K`` rows a bucket, the mean occupancy (padding is
  the implementation's own) — or, below a QLBT forest, the
  ``beam_width * tree_leaf`` rows its descent reaches where that is
  fewer; 2 * d flops per row;
* the queries themselves, read once.

The least time is the larger of the bytes over the HBM peak and the flops
over the float32 (``HIGHEST``, six bf16 passes) compute peak of
``bench/peaks.json``; ``describe`` names which bound applies."""

F32 = 4


def work(config: dict, dispatches: float, requests: float):
    """(bytes, flops) of ``dispatches`` dispatches serving ``requests``
    queries in all."""
    n, d = config["corpus"]["n"], config["corpus"]["d"]
    idx = config["index"]
    K, nprobe = idx["n_clusters"], idx["nprobe"]
    rows = n / K
    if idx["bottom"] == "qlbt":
        rows = min(rows, idx["beam_width"] * idx["tree_leaf"])
    per_query_rows = nprobe * rows
    nbytes = (dispatches * K * d * F32
              + requests * (per_query_rows + 1) * d * F32)
    flops = requests * 2.0 * d * (K + per_query_rows)
    return nbytes, flops


def _least(ctx):
    if ctx.device is None or ctx.peaks is None:
        return None
    dispatches, requests = ctx.batches
    if dispatches <= 0 or ctx.device["modules_s"] <= 0:
        return None
    nbytes, flops = work(ctx.config, dispatches, requests)
    t_hbm = nbytes / ctx.peaks["hbm_bytes_per_s"]
    t_mxu = flops / ctx.peaks["f32_highest_flops_per_s"]
    return max(t_hbm, t_mxu), "hbm" if t_hbm >= t_mxu else "compute"


def read(ctx):
    least = _least(ctx)
    if least is None:
        return None
    return 100.0 * least[0] / ctx.device["modules_s"]


def describe(ctx):
    least = _least(ctx)
    if least is None:
        return None
    return (f"search roofline: {least[1]}-bound, least {least[0]!r}s "
            f"against {ctx.device['modules_s']!r}s of device programs")
