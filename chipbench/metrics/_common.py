"""Arithmetic the per-layer metric readers share."""
from __future__ import annotations


import reduce_trace as traces
import yardstick


def device_s(ctx) -> float:
    """Wall seconds spent inside the executor's device calls."""
    return sum(b.t1 - b.t0 for b in ctx["batches"])


def roofline(ctx, kernel: str) -> float | None:
    """Share of its roofline one kernel reached over the traced window:
    the least time the chip could take for the work its calls had to do,
    counted from the shapes of each traced batch, over the device time of
    the kernel's events (the custom call is named after the kernel's
    function in ``src/repro/kernels``).  Each traced batch is matched to
    its ``device_call`` span, and counts only where the events inside the
    span are the calls the batch made (a profiler that drops events
    leaves that batch out).  Nothing when there is no trace or no batch
    matches."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    c, t = ctx["config"], ctx["traffic"]
    traced = [b for b in ctx["batches"] if b.traced]
    spans = [s for s in tr.host_spans if s.name == "device_call"]
    if not traced or len(spans) != len(traced):
        ctx["info"].append(f"{kernel}: {len(spans)} device_call spans for "
                           f"{len(traced)} traced batches; not reported")
        return None
    pk = ctx["peaks"]
    bound = secs = 0.0
    used = 0
    for b, span in zip(traced, spans):
        w = ctx["arch"].kernel_work(c, t["prompt_len"], t["gen_len"],
                                    b.bucket).get(kernel)
        if w is None:
            return None
        n, s = traces.op_time(tr, kernel, span.start, span.end)
        if n == w[2] and s > 0:
            bound += yardstick.bound_seconds(w[0], w[1], pk)
            secs += s
            used += 1
    ctx["info"].append(f"{kernel}: {used} of {len(traced)} traced batches "
                       f"with every call's event, {secs!r} s on the "
                       f"device, roofline bound {bound!r} s")
    return 100.0 * bound / secs if used else None
