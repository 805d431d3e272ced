"""Share of the traced window in which the device idled while the
innermost program span was ``simulate.events``: the scheduler's event loop
outside the executor (routing, SGS decisions, the periodic passes)."""
from metrics._program_spans import idle_pct


def read(ctx):
    return idle_pct(ctx, "scheduler")
