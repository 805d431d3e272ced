"""The idle readers that put device idle time down to the program's spans,
on a synthetic trace and synthetic program spans whose clock is offset
from the trace's by a known amount."""
import sys

import pytest

from chipbench_tiny import BENCH
import harness
from metrics import _program_spans
from reduce_trace import Event, Trace
from repro import spans
from repro.spans import Span

MS = 1_000_000           # ns
OFFSET = -7_340_000_000  # the trace's clock minus the program's
METRICS = ("idle_in_executor_pct", "idle_in_scheduler_pct",
           "idle_in_sim_setup_pct")


def _span(name, a, b, parent=None, lag=0):
    """A program span over [a, b] ms of the trace's clock."""
    return Span(name, {}, start=a * MS - OFFSET + lag,
                end=b * MS - OFFSET + lag, parent=parent)


def _program(lag_us=(3, 5)):
    """One segment (trace 0-100 ms): set-up 0-10, the event loop 10-90
    with two executor calls, the result 90-93 and its summary 93-95;
    95-100 is the harness's."""
    setup = _span("simulate.setup", 0, 10)
    events = _span("simulate.events", 10, 90)
    recs = [setup, events]
    for (a, b), lag in zip(((20, 40), (50, 80)), lag_us):
        run = _span("executor.run_batch", a, b, events, lag * 1000)
        lag *= 1000
        recs += [run, _span("executor.prefill", a + 1, a + 5, run, lag),
                 _span("executor.decode_step", a + 5, b - 2, run, lag),
                 _span("executor.sync", b - 2, b - 1, run, lag)]
    recs += [_span("simulate.result", 90, 93),
             _span("simulate.summary", 93, 95)]
    return recs


def _trace():
    # the device runs 22-37 and 53-77; idle elsewhere in 0-100 ms
    ops = [Event("fusion.1", 22 * MS, 37 * MS),
           Event("fusion.2", 53 * MS, 77 * MS)]
    calls = [Event("segment", 0, 100 * MS),
             Event("device_call", 20 * MS - 2000, 40 * MS + 9000),
             Event("device_call", 50 * MS - 2000, 80 * MS + 9000)]
    return Trace([ops], calls)


def _ctx(monkeypatch, recs, trace=None):
    monkeypatch.setattr(spans, "timeline", lambda: list(recs))
    return dict(trace=trace or _trace(), trace_lo=0.0, trace_hi=100 * MS,
                info=[])


def _read(name, ctx):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(ctx)


def test_offset_recovered_and_idle_put_down_to_the_innermost_span(
        monkeypatch):
    recs = _program()
    ctx = _ctx(monkeypatch, recs)
    runs = [r for r in recs if r.name == "executor.run_batch"]
    off, worst = _program_spans.align(
        runs, [s for s in ctx["trace"].host_spans if s.name == "device_call"])
    # each device_call starts 2 us before its run_batch, which lags by 3
    # and 5 us: the median puts the first 1 us before its device_call
    assert off == pytest.approx(OFFSET - 6000)
    assert worst == pytest.approx(1000)
    got = {m: _read(m, ctx) for m in METRICS}
    # (the spans land a few us off their nominal ms: abs 0.01% is 10 us)
    # executor: 20-22 and 37-40 (prefill, decode, sync and run_batch's own
    # time), 50-53 and 77-80
    assert got["idle_in_executor_pct"] == pytest.approx(11.0, abs=0.01)
    # the event loop's own time: 10-20, 40-50, 80-90
    assert got["idle_in_scheduler_pct"] == pytest.approx(30.0, abs=0.01)
    # set-up 0-10, the result and its summary 90-95
    assert got["idle_in_sim_setup_pct"] == pytest.approx(15.0, abs=0.01)
    idle = _read("device_idle_pct", ctx)
    assert idle == pytest.approx(61.0)
    assert sum(got.values()) <= idle
    table = "\n".join(ctx["info"])
    assert "worst alignment error" in table
    assert "simulate.summary 1 " in table and "(no program span)" in table
    tail = next(ln for ln in ctx["info"] if "(no program span)" in ln)
    assert float(tail.split()[-1]) == pytest.approx(0.005, abs=1e-5)


def test_a_misaligned_pair_is_not_reported(monkeypatch):
    ctx = _ctx(monkeypatch, _program(lag_us=(3, 150)))
    assert all(_read(m, ctx) is None for m in METRICS)
    assert any("outside its device_call" in ln for ln in ctx["info"])


def test_count_mismatch_or_no_spans_is_not_reported(monkeypatch):
    recs = [r for r in _program() if not (
        r.name.startswith("executor.") and r.start > 45 * MS - OFFSET)]
    ctx = _ctx(monkeypatch, recs)
    assert all(_read(m, ctx) is None for m in METRICS)
    assert any("1 executor.run_batch spans for 2 device_call" in ln
               for ln in ctx["info"])
    ctx = _ctx(monkeypatch, [])
    assert all(_read(m, ctx) is None for m in METRICS)
    assert ctx["info"] == ["program spans: none recorded; idle_in_* not "
                           "reported"]


def test_a_program_without_spans_is_not_reported(monkeypatch):
    import repro
    monkeypatch.delattr(repro, "spans")
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    ctx = dict(trace=_trace(), trace_lo=0.0, trace_hi=100 * MS, info=[])
    assert all(_read(m, ctx) is None for m in METRICS)
    assert "has no repro.spans" in ctx["info"][0]
    assert _read(METRICS[0], dict(trace=None, info=[])) is None
