"""Share of the traced window in which no operation ran on the device."""
import reduce_trace as traces


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    lo, hi = ctx["trace_lo"], ctx["trace_hi"]
    return 100.0 * (1.0 - traces.busy_ns(tr.device_ops[0], lo, hi)
                    / (hi - lo))
