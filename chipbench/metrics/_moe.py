"""The expert layer's counters of each traced batch, as the program hands
them to the tracing: attributes of the batch's ``executor.sync`` span
(``repro.spans``), summed on the device over its prefill
(``moe_prefill_*``) and its decode steps (``moe_decode_*``): rows that fell
on held experts, held experts with rows (the weight fetches), and rows
dropped."""
from __future__ import annotations


def batch_counters(ctx):
    """One dict of counters per traced batch, in order; None (with the
    reason in ``ctx["info"]``) where the program records none."""
    info = ctx["info"]
    try:
        from repro import spans
    except ImportError:
        info.append("expert counters: the program has no repro.spans")
        return None
    recs = spans.timeline()
    runs = [r for r in recs if r.name == "executor.run_batch"]
    syncs = {id(r.parent): r for r in recs if r.name == "executor.sync"}
    traced = [b for b in ctx["batches"] if b.traced]
    if not traced or len(runs) != len(traced):
        info.append(f"expert counters: {len(runs)} executor.run_batch "
                    f"spans for {len(traced)} traced batches")
        return None
    out = []
    for r in runs:
        s = syncs.get(id(r))
        if s is None or "moe_decode_rows" not in s.attrs:
            info.append("expert counters: an executor.sync span carries "
                        "none")
            return None
        out.append(s.attrs)
    dropped = sum(a["moe_prefill_dropped"] + a["moe_decode_dropped"]
                  for a in out)
    info.append(f"expert counters: {len(out)} batches, rows "
                f"{sum(a['moe_prefill_rows'] for a in out)} prefill / "
                f"{sum(a['moe_decode_rows'] for a in out)} decode, fetches "
                f"{sum(a['moe_prefill_fetches'] for a in out)} / "
                f"{sum(a['moe_decode_fetches'] for a in out)}, dropped "
                f"{dropped} (moe_dropped, in every batch: "
                f"{[a['moe_prefill_dropped'] + a['moe_decode_dropped'] for a in out]})")
    return out
