"""Share of the traced window in which the device idled while the
innermost program span was ``simulate.setup``, ``simulate.result`` or
``simulate.summary``: ``simulate``'s own work before and after its event
loop."""
from metrics._program_spans import idle_pct


def read(ctx):
    return idle_pct(ctx, "sim_setup")
