"""Device idle time put down to the program's own spans.

The program records its spans (``repro.spans``) while the profiler runs, on
the host's ``perf_counter_ns`` clock; the trace's times are relative to the
profiler's session.  Each program ``executor.run_batch`` span wraps the same
call as the harness's ``device_call`` span, so the two are paired one to
one, the median of their start differences is taken as the offset between
the clocks, and every shifted ``run_batch`` must lie inside its
``device_call`` within ``ALIGN_NS``.  Each idle interval of the device
between ``trace_lo`` and ``trace_hi`` then goes to the innermost program
span open at that instant (a span's self time: its length less that of the
spans inside it), and the span names fall into three categories.
Idle time under no program span is the harness's own.
"""
from __future__ import annotations

import bisect
import statistics

import reduce_trace as traces

ALIGN_NS = 50_000
MEMO = "program_span_idle"


def category(name: str) -> str | None:
    """The idle metric a span's self time counts towards."""
    if name.startswith("executor."):
        return "executor"
    if name == "simulate.events":
        return "scheduler"
    if name.startswith("simulate."):
        return "sim_setup"
    return None


def idle_pct(ctx, cat: str) -> float | None:
    """Share of the traced window in which the device idled while the
    innermost program span was of category ``cat``; None where the spans
    cannot be read or put on the trace's clock (the reason goes to
    ``ctx["info"]``)."""
    if MEMO not in ctx:
        ctx[MEMO] = attribute(ctx)
    shares = ctx[MEMO]
    return None if shares is None else shares[cat]


def _records(info):
    try:
        from repro import spans
    except ImportError:
        info.append("program spans: the program has no repro.spans; "
                    "idle_in_* not reported")
        return None
    recs = spans.timeline()
    if not recs:
        info.append("program spans: none recorded; idle_in_* not reported")
        return None
    return recs


def align(runs, calls):
    """The offset from the program's clock to the trace's, and the worst
    distance (ns) by which a shifted ``run_batch`` leaves its
    ``device_call``; None where the two cannot be paired."""
    if not runs or len(runs) != len(calls):
        return None
    off = statistics.median(c.start - r.start for r, c in zip(runs, calls))
    worst = max(max(0.0, c.start - (r.start + off), (r.end + off) - c.end)
                for r, c in zip(runs, calls))
    return off, worst


def attribute(ctx) -> dict | None:
    """Each category's share (%) of the traced window idled under its
    spans, with the attribution table appended to ``ctx["info"]``."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    info = ctx["info"]
    recs = _records(info)
    if recs is None:
        return None
    runs = [r for r in recs if r.name == "executor.run_batch"]
    calls = [s for s in tr.host_spans if s.name == "device_call"]
    got = align(runs, calls)
    if got is None:
        info.append(f"program spans: {len(runs)} executor.run_batch spans "
                    f"for {len(calls)} device_call spans; idle_in_* not "
                    f"reported")
        return None
    off, worst = got
    if worst > ALIGN_NS:
        info.append(f"program spans: an executor.run_batch span lies "
                    f"{worst * 1e-3:.1f} us outside its device_call (limit "
                    f"{ALIGN_NS * 1e-3:.0f} us); idle_in_* not reported")
        return None
    lo, hi = ctx["trace_lo"], ctx["trace_hi"]
    gaps = traces.gaps(tr.device_ops[0], lo, hi)
    starts = [a for a, _ in gaps]
    cum = [0.0]
    for a, b in gaps:
        cum.append(cum[-1] + (b - a))

    def idle_before(t):
        """Idle time of the device in [lo, t]."""
        i = bisect.bisect_right(starts, t)
        if i == 0:
            return 0.0
        a, b = gaps[i - 1]
        return cum[i - 1] + min(t, b) - a

    def idle(a, b):
        a, b = max(a, lo), min(b, hi)
        return idle_before(b) - idle_before(a) if b > a else 0.0

    index = {id(r): k for k, r in enumerate(recs)}
    whole = [idle(r.start + off, r.end + off) for r in recs]
    own = [r.end - r.start for r in recs]
    own_idle = list(whole)
    for k, r in enumerate(recs):
        p = index.get(id(r.parent))
        if p is not None:
            own[p] -= r.end - r.start
            own_idle[p] -= whole[k]
    table = {}
    for k, r in enumerate(recs):
        row = table.setdefault(r.name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += own[k]
        row[2] += own_idle[k]
    window = hi - lo
    shares = {"executor": 0.0, "scheduler": 0.0, "sim_setup": 0.0}
    spanned = 0.0
    for name, (_, _, idle_ns) in table.items():
        spanned += idle_ns
        cat = category(name)
        if cat is not None:
            shares[cat] += 100.0 * idle_ns / window
    total = cum[-1]
    info.append(f"program spans: {len(runs)} executor.run_batch spans "
                f"paired with their device_call spans, offset {off!r} ns, "
                f"worst alignment error {worst * 1e-3:.3f} us")
    info.append("program spans: name count self_s idle_s "
                "(device idle inside the span's self time)")
    for name, (n, own_ns, idle_ns) in sorted(table.items(),
                                              key=lambda kv: -kv[1][2]):
        info.append(f"  {name} {n} {own_ns * 1e-9!r} {idle_ns * 1e-9!r}")
    info.append(f"  (no program span) - - {(total - spanned) * 1e-9!r}")
    return shares
