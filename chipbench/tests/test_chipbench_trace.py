"""The trace reduction on a small synthetic trace."""
import pytest

import chipbench_tiny  # noqa: F401  (puts the benchmark on sys.path)
import reduce_trace as traces
from reduce_trace import Event, Trace

MS = 1e6  # ns


def _trace():
    ops = [Event("fusion.1", 0 * MS, 10 * MS, "jit__decode"),
           Event("while.3", 11 * MS, 52 * MS, "jit__prefill"),    # a loop
           Event("flash_attention.4", 12 * MS, 20 * MS, "jit__prefill"),
           Event("fusion.2", 15 * MS, 25 * MS, "jit__prefill"),   # overlaps
           Event("flash_attention.7", 40 * MS, 50 * MS, "jit__prefill"),
           Event("fusion.1", 90 * MS, 100 * MS, "jit__decode")]
    spans = [Event("segment", 0, 100 * MS),
             Event("device_call", 0, 55 * MS)]
    return Trace([ops], spans)


def test_busy_and_idle_share():
    tr = _trace()
    # busy: [0,10] [11,52] [90,100] = 10 + 41 + 10
    assert traces.busy_ns(tr.device_ops[0], 0, 100 * MS) == pytest.approx(61 * MS)
    assert traces.busy_ns(tr.device_ops[0], 5 * MS, 95 * MS) == pytest.approx(51 * MS)


def test_gaps_named_by_innermost_host_span():
    tr = _trace()
    got = traces.longest_gaps(tr, 0, 100 * MS, k=3)
    # gaps: [10,11] [52,90]; the longest lies outside the device call
    assert [round(s * 1e3, 6) for _, s in got] == [38.0, 1.0]
    assert got[0][0] == traces.GAP_LABELS["segment"]
    assert got[1][0] == traces.GAP_LABELS["device_call"]


def test_per_kernel_time_and_top_ops():
    tr = _trace()
    n, secs = traces.op_time(tr, "flash_attention", 0, 100 * MS)
    assert n == 2 and secs == pytest.approx(18e-3)
    top = traces.top_ops(tr, 0, 100 * MS, k=2)      # the loop is left out
    assert top[0] == ["jit__decode/fusion.1", pytest.approx(20e-3)]
    assert top[1] == ["jit__prefill/fusion.2", pytest.approx(10e-3)]


def test_op_names_from_tpu_event_text():
    text = ("%decode_attention.10 = bf16[32,1,96]{2,1,0} custom-call("
            "s32[32]{0} %broadcast.196), custom_call_target=\"tpu_custom_call\"")
    assert traces.op_name(text) == "decode_attention.10"
    assert traces.kind("decode_attention.10") == "decode_attention"
    assert traces.kind("fusion") == "fusion"


def test_idle_window_with_no_ops():
    tr = Trace([[]], [])
    assert traces.busy_ns([], 0, 10) == 0
    assert traces.longest_gaps(tr, 0, 10 * MS) == [[traces.GAP_LABELS[None], pytest.approx(0.01)]]
