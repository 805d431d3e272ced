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


def test_ops_take_the_module_that_encloses_them():
    """Each op's program is the module event around it; the breakdown's
    keys stay the op's own (``module/name`` only with an ``hlo_module``)."""
    from types import SimpleNamespace

    def raw(text, start, end, stats=()):
        return SimpleNamespace(name=text, start_ns=start, end_ns=end,
                               stats=list(stats))

    mods = [Event("jit__prefill(3)", 0, 10 * MS),
            Event("jit__decode(4)", 20 * MS, 30 * MS)]
    ops = [raw("%copy.1 = f32[8]{0} copy(f32[8]{0} %p)", 1 * MS, 2 * MS),
           raw("%copy.1 = f32[8]{0} copy(f32[8]{0} %p)", 21 * MS, 22 * MS),
           raw("fusion.2", 12 * MS, 13 * MS),
           raw("fusion.3", 22 * MS, 24 * MS, [("hlo_module", "jit__other")])]
    got = traces._device_events(ops, mods)
    assert [e.name for e in got] == ["copy.1", "copy.1", "fusion.2",
                                     "fusion.3"]
    assert [e.program for e in got] == ["jit__prefill(3)", "jit__decode(4)",
                                        "", "jit__decode(4)"]
    assert [e.module for e in got] == ["", "", "", "jit__other"]
    top = traces.top_ops(Trace([got], []), 0, 30 * MS)
    assert [n for n, _ in top] == ["copy.1", "jit__other/fusion.3",
                                   "fusion.2"]


def test_module_text_from_the_profile(tmp_path):
    """A CPU profile carries each compiled program's HLO in its metadata
    plane, named ``name(program_id)``; ``read_xspace`` returns its text."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 3.0)
    x = jnp.ones((8, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.segment"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = traces.read_xspace(str(tmp_path), "chipbench.")
    assert [s.name for s in tr.host_spans] == ["segment"]
    [name] = [k for k in tr.hlo if k.startswith("jit__lambda(")]
    assert name in tr.hlo and tr.hlo.made() == 0    # text made when asked
    assert name.endswith(")") and "HloModule jit__lambda" in tr.hlo[name]
    assert "sine" in tr.hlo[name]
    assert tr.hlo.made() == 1 and tr.hlo.error == ""
