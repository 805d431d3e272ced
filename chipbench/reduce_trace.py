"""Reduction of a profiler trace to device busy time, per-operation device
time, and idle gaps attributed to the host spans around them.

Works on plain lists of events, so tests can feed it a synthetic trace:
``Event(name, start_ns, end_ns, module)``.  ``read_xspace`` turns the
profiler's ``.xplane.pb`` into those lists.
"""
from __future__ import annotations

import glob
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclass(frozen=True)
class Event:
    name: str
    start: float        # ns
    end: float          # ns
    module: str = ""


@dataclass
class Trace:
    device_ops: List[List[Event]]      # one list per device
    host_spans: List[Event]            # the harness's own annotations


def read_xspace(trace_dir: str, span_prefix: str) -> Trace:
    """Device op events (line ``XLA Ops`` of each TPU plane) and the host
    annotations whose name starts with ``span_prefix``."""
    from jax.profiler import ProfileData
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"want one trace file under {trace_dir}, found "
                           f"{len(files)}")
    pd = ProfileData.from_file(files[0])
    devices: List[List[Event]] = []
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([_device_event(e) for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append(Event(e.name[len(span_prefix):],
                                           e.start_ns, e.end_ns))
    return Trace(devices, spans)


def op_name(text: str) -> str:
    """The HLO instruction's name from an op event's name, which on a TPU
    is the instruction's text: ``%decode_attention.10 = bf16[...] ...``
    -> ``decode_attention.10``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _device_event(e) -> Event:
    stats = dict(e.stats)
    return Event(op_name(e.name), e.start_ns, e.end_ns,
                 str(stats.get("hlo_module", "")))


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Disjoint busy intervals covered by ``events``."""
    out: List[Tuple[float, float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            if e.end > out[-1][1]:
                out[-1] = (out[-1][0], e.end)
        else:
            out.append((e.start, e.end))
    return out


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Time in [lo, hi] during which some device op ran."""
    return sum(max(0.0, min(b, hi) - max(a, lo))
               for a, b in union(events))


def gaps(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of the device within [lo, hi]."""
    out, t = [], lo
    for a, b in union(events):
        if b <= lo:
            continue
        if a >= hi:
            break
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def span_at(spans: Sequence[Event], t: float) -> Optional[Event]:
    """The innermost harness span that contains instant ``t``."""
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or
                                      s.end - s.start < best.end - best.start):
            best = s
    return best


GAP_LABELS = {
    "device_call": "host inside executor calls (dispatch, argmax, sync)",
    "segment": "scheduler and simulator between executor calls",
    None: "harness between segments",
}


def longest_gaps(tr: Trace, lo: float, hi: float, k: int = 10
                 ) -> List[List]:
    """The ``k`` longest idle gaps of the first device, each named by the
    innermost host span at its midpoint: [[label, seconds], ...]."""
    gs = sorted(gaps(tr.device_ops[0], lo, hi), key=lambda g: g[0] - g[1])
    out = []
    for a, b in gs[:k]:
        s = span_at(tr.host_spans, (a + b) / 2)
        out.append([GAP_LABELS.get(s.name if s else None,
                                   s.name if s else "harness"),
                    (b - a) * 1e-9])
    return out


def kind(name: str) -> str:
    """An instruction's kind: its name without the numeric suffix
    (``decode_attention.10`` -> ``decode_attention``)."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


# control-flow ops whose events enclose the ops of their bodies
CONTAINERS = ("while", "conditional", "call")


def top_ops(tr: Trace, lo: float, hi: float, k: int = 10) -> List[List]:
    """Device operations by total time in [lo, hi] on the first device,
    loops and calls left out (their bodies' ops are counted):
    [[module/op, seconds], ...]."""
    tot: Dict[str, float] = {}
    for e in tr.device_ops[0]:
        if e.end > lo and e.start < hi and kind(e.name) not in CONTAINERS:
            key = f"{e.module}/{e.name}" if e.module else e.name
            tot[key] = tot.get(key, 0.0) + (min(e.end, hi) - max(e.start, lo))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in best]


def op_time(tr: Trace, op_kind: str, lo: float, hi: float
            ) -> Tuple[int, float]:
    """Number and summed device seconds of the ops of kind ``op_kind`` in
    [lo, hi] on the first device."""
    n, t = 0, 0.0
    for e in tr.device_ops[0]:
        if e.start >= lo and e.end <= hi and kind(e.name) == op_kind:
            n += 1
            t += e.end - e.start
    return n, t * 1e-9
