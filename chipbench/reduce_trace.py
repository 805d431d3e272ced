"""Reduction of a profiler trace to device busy time, per-operation device
time, and idle gaps attributed to the host spans around them.

Works on plain lists of events, so tests can feed it a synthetic trace:
``Event(name, start_ns, end_ns, module)``.  ``read_xspace`` turns the
profiler's ``.xplane.pb`` into those lists, with the HLO module of each
program the profile carries.
"""
from __future__ import annotations

import bisect
import glob
import time
from collections.abc import Mapping
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"


@dataclass(frozen=True)
class Event:
    name: str
    start: float        # ns
    end: float          # ns
    module: str = ""
    program: str = ""   # the program run around it, ``name(program_id)``


@dataclass
class Trace:
    device_ops: List[List[Event]]      # one list per device
    host_spans: List[Event]            # the harness's own annotations
    # module name -> HLO text, as the profile carries them; None for a
    # trace that carries no module information (a synthetic one)
    hlo: Optional[Mapping] = None
    # per device, the transfers of asynchronous copies (line ``Async XLA
    # Ops``): each named after its ``copy-start`` and lasting until its
    # ``copy-done``, where the ``XLA Ops`` line has only the two issues
    async_ops: Optional[List[List[Event]]] = None


def read_xspace(trace_dir: str, span_prefix: str) -> Trace:
    """Device op events (line ``XLA Ops`` of each TPU plane), the
    transfers of asynchronous copies, and the host annotations whose name
    starts with ``span_prefix``."""
    from jax.profiler import ProfileData
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"want one trace file under {trace_dir}, found "
                           f"{len(files)}")
    with open(files[0], "rb") as f:
        raw = f.read()
    pd = ProfileData.from_serialized_xspace(raw)
    devices: List[List[Event]] = []
    transfers: List[List[Event]] = []
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                mods = [Event(e.name, e.start_ns, e.end_ns) for e in
                        getattr(lines.get(MODULES_LINE), "events", ())]
                devices.append(_device_events(lines[OPS_LINE].events, mods))
                transfers.append(_device_events(
                    getattr(lines.get(ASYNC_LINE), "events", ()), mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append(Event(e.name[len(span_prefix):],
                                           e.start_ns, e.end_ns))
    return Trace(devices, spans, HloModules(raw), transfers)


def op_name(text: str) -> str:
    """The HLO instruction's name from an op event's name, which on a TPU
    is the instruction's text: ``%decode_attention.10 = bf16[...] ...``
    -> ``decode_attention.10``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _device_events(events, mods: List[Event]) -> List[Event]:
    """The op events of one line of a device, each with the module event
    of the device that encloses it as its ``program`` (the ``XLA Modules``
    line names each run of a program ``name(program_id)``, as the
    metadata plane names its HLO)."""
    mods = sorted(mods, key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for e in events:
        start, end = e.start_ns, e.end_ns
        i = bisect.bisect_right(starts, start) - 1
        program = mods[i].name if i >= 0 and end <= mods[i].end else ""
        out.append(Event(op_name(e.name), start, end,
                         str(dict(e.stats).get("hlo_module", "")), program))
    return out


# ---------------------------------------------------------------------------
# The HLO modules a profile carries: the ``/host:metadata`` plane holds one
# event metadata per program, named ``name(program_id)``, whose ``Hlo Proto``
# stat is a serialized ``xla.HloProto``.  ``ProfileData`` does not expose
# event metadata, so the few fields needed are read off the protobuf wire,
# as views into the file's bytes: a plane is skipped on its name, and a
# module is turned into text only when a reader asks for it.
# ---------------------------------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    r = shift = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return r, i


def _scan(buf: memoryview) -> Iterator[Tuple[int, object]]:
    """The fields of one protobuf message in their order: (number, value),
    an int for a varint, a view for a length-delimited or fixed-width
    one."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield num, v


def _fields(buf: memoryview) -> Dict[int, List]:
    """The fields of one protobuf message: number -> values."""
    out: Dict[int, List] = {}
    for num, v in _scan(buf):
        out.setdefault(num, []).append(v)
    return out


def hlo_protos(xspace) -> Dict[str, bytes]:
    """Module name (``name(program_id)``) -> serialized ``HloProto``, for
    every module in the serialized XSpace's metadata plane (XSpace.planes
    = 1; XPlane.name = 2, event_metadata = 4, stat_metadata = 5;
    XEventMetadata.name = 2, stats = 5; XStat.metadata_id = 1,
    bytes_value = 6)."""
    out: Dict[str, bytes] = {}
    for num, plane in _scan(memoryview(xspace)):
        if num != 1:
            continue
        name = next((bytes(v) for k, v in _scan(plane) if k == 2), b"")
        if name.decode() != METADATA_PLANE:
            continue
        p = _fields(plane)
        stat_names = {}
        for entry in p.get(5, []):
            md = _fields(_fields(entry)[2][0])
            stat_names[md.get(1, [0])[0]] = bytes(md.get(2, [b""])[0])
        for entry in p.get(4, []):
            md = _fields(_fields(entry)[2][0])
            for st in md.get(5, []):
                st = _fields(st)
                if (stat_names.get(st.get(1, [0])[0]) == b"Hlo Proto"
                        and 6 in st):
                    out[bytes(md[2][0]).decode()] = bytes(st[6][0])
    return out


class HloModules(Mapping):
    """Module name -> HLO text of the programs a profile carries.  The
    names are read with the trace; a module's text is made from its
    ``HloProto`` (``HloProto.hlo_module`` = 1) the first time it is asked
    for.  ``scan_s`` and ``text_s`` are the seconds each step took;
    ``error`` says why a profile whose metadata cannot be read carries no
    module."""

    def __init__(self, xspace):
        t = time.perf_counter()
        self.error = ""
        try:
            self._protos = hlo_protos(xspace)
        except (ValueError, IndexError, KeyError, UnicodeDecodeError) as e:
            self._protos, self.error = {}, repr(e)
        self.scan_s = time.perf_counter() - t
        self.text_s = 0.0
        self._texts: Dict[str, str] = {}

    def __getitem__(self, name: str) -> str:
        if name not in self._texts:
            proto = self._protos[name]
            t = time.perf_counter()
            from jaxlib._jax import HloModule
            module = _fields(memoryview(proto))[1][0]
            self._texts[name] = HloModule.from_serialized_hlo_module_proto(
                bytes(module)).to_string()
            self.text_s += time.perf_counter() - t
        return self._texts[name]

    def __contains__(self, name) -> bool:
        return name in self._protos

    def __iter__(self) -> Iterator[str]:
        return iter(self._protos)

    def __len__(self) -> int:
        return len(self._protos)

    def made(self) -> int:
        """How many modules were turned into text."""
        return len(self._texts)


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
