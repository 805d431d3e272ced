"""Requests per executed batch, from the coalescer's counters."""


def read(ctx):
    c = ctx["counters"]
    return c["n_batched_invocations"] / c["n_batches"] if c.get(
        "n_batches") else None
