"""Requests per dispatch over the cell's ``max_batch``, from the cells'
``batch_size`` histogram sums and counts across the traced window."""


def read(ctx):
    dispatches, requests = ctx.batches
    if dispatches <= 0:
        return None
    return requests / dispatches / ctx.config["serve"]["max_batch"]
