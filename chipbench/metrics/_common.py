"""Arithmetic the per-layer metric readers share."""
from __future__ import annotations

import bisect
from collections import Counter
from typing import Dict, List, Optional, Tuple

import hlo
import reduce_trace as traces
import yardstick


def device_s(ctx) -> float:
    """Wall seconds spent inside the executor's device calls."""
    return sum(b.t1 - b.t0 for b in ctx["batches"])


class Unmatched(Exception):
    """A kernel's module cannot be matched to the trace."""


def _module(tr, name: str, cache: Dict[str, hlo.Module]) -> hlo.Module:
    """The HLO module an event's program names (``name(program_id)``)."""
    if name not in cache:
        if name not in tr.hlo:
            why = getattr(tr.hlo, "error", "")
            raise Unmatched(f"module {name!r} is not among the "
                            f"{len(tr.hlo)} the profile carries"
                            + (f" ({why})" if why else ""))
        try:
            cache[name] = hlo.Module(tr.hlo[name])
        except (ImportError, RuntimeError, ValueError, IndexError,
                KeyError) as e:
            raise Unmatched(f"module {name!r} cannot be read: {e!r}"
                            ) from None
    return cache[name]


def _staged(tr, key: Tuple[str, str], operands,
            cache: Dict) -> List[hlo.Operand]:
    if key not in cache:
        module, name = key
        mod = _module(tr, module, cache)
        try:
            cache[key] = hlo.staging(mod, name, *operands)
        except KeyError as e:
            raise Unmatched(f"{e.args[0]} {module!r}") from None
    return cache[key]


def _inside(evs: List[traces.Event], starts: List[float], span
            ) -> List[traces.Event]:
    """The events (sorted by start) that lie inside ``span``."""
    return [e for e in evs[bisect.bisect_left(starts, span.start):
                           bisect.bisect_right(starts, span.end)]
            if e.end <= span.end]


def roofline(ctx, kernel: str, operands: Tuple[Tuple, Tuple]
             ) -> Optional[float]:
    """Share of its roofline one kernel reached over the traced window.

    Each traced batch is matched to its ``device_call`` span.  Its bound
    is the least time the chip could take for the work of the batch's
    calls, ``max(FLOPs / peak, bytes / bandwidth)`` from the
    ``yardstick`` cost of the batch's shapes, counting only the bytes of
    the operands that cross HBM: for each kernel instruction that ran,
    the compiled module (the profile's own HLO) says where each operand
    lives (``operands`` names the custom call's inputs and outputs by the
    parts of the cost), and an operand staged in VMEM counts only where
    a chain of data-movement ops joins it to HBM (``hlo.staging``).  The
    time is the span's time covered by the kernel's events and by the
    events of those ops, an asynchronous copy by its transfer (the trace's
    ``async_ops``), not by its issue alone; an op with no event anywhere
    in the trace runs no device op and adds nothing, and a transfer that
    the trace lacks leaves the kernel unmatched.  A batch counts only
    where the span holds every call's event and, for each op charged, at
    least as many events as the kernel instruction it serves (a profiler
    that drops events leaves that batch out).  Nothing when there is no
    trace or no batch counts, or, with the reason, when a kernel's module
    cannot be matched to the trace.  A trace that carries no module text
    (a synthetic one) counts every operand, with the kernel's time
    alone."""
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
    cache: Dict = {}
    books: Dict[int, Dict] = {}
    ops = sorted(tr.device_ops[0], key=lambda e: e.start)
    starts = [e.start for e in ops]
    moves = sorted((tr.async_ops or [[]])[0], key=lambda e: e.start)
    move_starts = [e.start for e in moves]
    ran = {(e.program, e.name) for e in ops}    # ops that run on the device
    moved = {(e.program, e.name) for e in moves}
    for b, span in zip(traced, spans):
        w = ctx["arch"].kernel_work(c, t["prompt_len"], t["gen_len"],
                                    b.bucket).get(kernel)
        if w is None:
            return None
        flops, parts, calls = w
        evs = _inside(ops, starts, span)
        kern = [e for e in evs if traces.kind(e.name) == kernel]
        if len(kern) != calls:
            continue
        counted, dropped = float(sum(parts.values())), Counter()
        need: Counter = Counter()
        need_moved: Counter = Counter()
        if tr.hlo is not None:
            counted = 0.0
            try:
                instrs = Counter((e.program, e.name) for e in kern)
                for key, n in instrs.items():
                    moving, timed = set(), set()
                    for o in _staged(tr, key, operands, cache):
                        share = n / calls * parts[o.role]
                        if o.hbm:
                            counted += share
                        else:
                            dropped[f"{o.role} at {o.end}"] += share
                        moving.update((key[0], op) for op in o.charged)
                        timed.update((key[0], op) for op in o.transfers)
                    lost = sorted(op for _, op in timed - moved)
                    if lost:
                        raise Unmatched(f"the transfer of {', '.join(lost)} "
                                        f"in {key[0]!r} is not in the trace")
                    need.update({op: n for op in moving if op in ran})
                    need_moved.update({op: n for op in timed})
            except Unmatched as e:
                ctx["info"].append(f"{kernel}: {e}; not reported")
                return None
        charged = [e for e in evs if (e.program, e.name) in need]
        transfers = [e for e in _inside(moves, move_starts, span)
                     if (e.program, e.name) in need_moved]
        have = Counter((e.program, e.name) for e in charged)
        have_moved = Counter((e.program, e.name) for e in transfers)
        if (any(have[k] < n for k, n in need.items())
                or any(have_moved[k] < n for k, n in need_moved.items())):
            continue
        kern_s = sum(e.end - e.start for e in kern) * 1e-9
        op_s: Counter = Counter()
        for e in charged:
            op_s[e.name] += (e.end - e.start) * 1e-9
        for e in transfers:
            op_s[f"{e.name} transfer"] += (e.end - e.start) * 1e-9
        bound += yardstick.bound_seconds(flops, counted, pk)
        secs += sum(hi - lo for lo, hi in
                    traces.union(kern + charged + transfers)) * 1e-9
        used += 1
        bk = books.setdefault(b.bucket, dict(
            n=0, kernel_s=0.0, ops=Counter(), counted=0.0,
            dropped=Counter(), instrs=set()))
        bk["n"] += 1
        bk["kernel_s"] += kern_s
        bk["ops"].update(op_s)
        bk["counted"] += counted
        bk["dropped"].update(dropped)
        bk["instrs"].update(f"{m}/{n}" if m else n for m, n in
                            {(e.program, e.name) for e in kern})
    ctx["info"].append(f"{kernel}: {used} of {len(traced)} traced batches "
                       f"with every call's and every charged op's events, "
                       f"{secs!r} s on the device, roofline bound "
                       f"{bound!r} s")
    for bucket, bk in sorted(books.items()):
        listed = ", ".join(f"{n} {s!r} s"
                           for n, s in sorted(bk["ops"].items()))
        drop = ", ".join(f"{r} {v!r} B" for r, v in sorted(
            bk["dropped"].items()))
        ctx["info"].append(
            f"{kernel} bucket {bucket} ({bk['n']} batches, "
            f"{', '.join(sorted(bk['instrs']))} {bk['kernel_s']!r} s): "
            f"charged ops [{listed or 'none'}]; bytes counted "
            f"{bk['counted']!r}, dropped [{drop or 'none'}]")
    return 100.0 * bound / secs if used else None
