"""Cold starts per completed invocation (single-function DAGs: one
invocation per request), from the scheduler's per-request counts."""


def read(ctx):
    cold = sum(s.cold_starts for s in ctx["segments"])
    return 100.0 * cold / ctx["completed"]
