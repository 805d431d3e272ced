"""Share of the traced window in which the device idled while the
innermost program span was ``executor.run_batch`` or one inside it
(prefill, decode steps, the final sync): the data plane's own host time."""
from metrics._program_spans import idle_pct


def read(ctx):
    return idle_pct(ctx, "executor")
