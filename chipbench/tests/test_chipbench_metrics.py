"""Each per-layer metric's reader on a synthetic context: found by its
file name, reading spans, counters and a small trace."""
import pytest

import chipbench_tiny
from chipbench_tiny import BENCH
import harness
import yardstick
from harness import Batch, Segment
from reduce_trace import Event, Trace

MS = 1e6


def _ctx(kind, with_trace=True):
    b = chipbench_tiny.bundle(kind)
    c, t = b["config"], b["traffic"]
    arch = harness.arch_module(c)
    batches = [Batch(n=3, bucket=4, t0=0.0, t1=0.5, traced=True),
               Batch(n=1, bucket=1, t0=1.0, t1=1.25, traced=True)]
    segs = [Segment(attempted=4, completed=4, met=4, cold_starts=1,
                    latencies=[0.1] * 4, lost=0, duplicates=0, pending=0,
                    counters={}, t0=0.0, t1=2.0)]
    ctx = dict(segments=segs, window_s=2.0, batches=batches,
               counters={"n_batches": 2, "n_batched_invocations": 4},
               completed=4, config=c, traffic=t, arch=arch,
               peaks=yardstick.peaks("TPU v5 lite"), info=[], trace=None)
    if with_trace:
        kern = {"phi3": ["flash_attention", "decode_attention"],
                "mamba2": ["ssd_scan"]}[kind]
        ops, spans, t0 = [], [], 0.0
        for bt in batches:
            work = arch.kernel_work(c, t["prompt_len"], t["gen_len"],
                                    bt.bucket)
            start = t0
            for name, kk in zip(kern, work):
                for _ in range(work[kk][2]):
                    ops.append(Event(f"{name}.{len(ops)}", t0, t0 + 0.01 * MS))
                    t0 += 0.02 * MS
            spans.append(Event("device_call", start, t0))
        ctx.update(trace=Trace([ops], spans), trace_lo=0.0, trace_hi=t0)
    return b, ctx


def _read(name, ctx):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(ctx)


@pytest.mark.parametrize("kind", ["phi3", "mamba2"])
def test_span_and_counter_readers(kind):
    _, ctx = _ctx(kind)
    assert _read("host_ms_per_req", ctx) == pytest.approx(1e3 * 1.25 / 4)
    assert _read("device_call_ms_per_req", ctx) == pytest.approx(1e3 * 0.75 / 4)
    assert _read("batch_occupancy", ctx) == 2.0
    assert _read("cold_start_pct", ctx) == 25.0
    t = ctx["traffic"]
    want = (100.0 * 4 * ctx["arch"].model_flops(ctx["config"], t["prompt_len"],
                                                 t["gen_len"]) / 2.0 / 197e12)
    assert _read("mfu", ctx) == pytest.approx(want)
    assert _read("device_idle_pct", ctx) == pytest.approx(50.0)


@pytest.mark.parametrize("kind,metrics", [
    ("phi3", ["flash_attention_roofline", "decode_attention_roofline"]),
    ("mamba2", ["ssd_scan_roofline"])])
def test_roofline_readers(kind, metrics):
    _, ctx = _ctx(kind)
    for m in metrics:
        v = _read(m, ctx)
        assert v is not None and 0.0 < v
    # no trace: nothing
    _, bare = _ctx(kind, with_trace=False)
    assert all(_read(m, bare) is None for m in metrics)
    # a batch whose events are not all in the trace is left out: the
    # reading is then the first batch's alone, and nothing when no batch
    # has all of its events
    m = metrics[-1]
    ops = ctx["trace"].device_ops[0]
    ops.pop()
    _, first = _ctx(kind)
    first["batches"] = first["batches"][:1]
    first["trace"].host_spans[1:] = []
    assert _read(m, ctx) == pytest.approx(_read(m, first))
    kernel = m[:-len("_roofline")]
    span0 = ctx["trace"].host_spans[0]
    ops.remove(next(e for e in ops if e.end <= span0.end
                    and e.name.startswith(kernel)))
    assert _read(m, ctx) is None
