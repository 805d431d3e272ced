"""Program spans (``repro.spans``): nothing recorded while no profiler runs,
nesting and self time when recording, the ``archipelago.`` twins in a real
profiler trace, and no change to what the program decides."""
import glob
import json
import pathlib
import sys
import time
from dataclasses import replace

import pytest

from repro import spans
from repro.core import ClusterConfig
from repro.sim import Experiment, simulate

ROOT = pathlib.Path(__file__).resolve().parent.parent
GOLDEN = ROOT / "tests" / "data" / "golden_equivalence.json"
sys.path.insert(0, str(ROOT))

from benchmarks.equivalence_fingerprint import CONFIGS, fingerprint_one  # noqa: E402


@pytest.fixture
def recording():
    spans.reset()
    spans.force(True)
    try:
        yield
    finally:
        spans.force(False)
        spans.reset()


def test_profiler_off_records_nothing_and_shares_one_no_op():
    spans.reset()
    a = spans.span("executor.run_batch", bucket=1, n=1, members=[3])
    b = spans.span("executor.sync")
    assert a is b is spans.NO_SPAN
    with spans.span("simulate.events") as s:
        assert s is None
    assert spans.timeline() == []


def test_forced_records_nest_with_parents_and_self_time(recording):
    with spans.span("simulate.events") as outer:
        time.sleep(0.02)
        with spans.span("executor.run_batch", bucket=2, n=2,
                        members=[5, 4]) as inner:
            time.sleep(0.01)
        with spans.span("executor.sync") as second:
            pass
    got = spans.timeline()
    assert got == [outer, inner, second]
    assert outer.parent is None
    assert inner.parent is outer and second.parent is outer
    assert inner.attrs == {"bucket": 2, "n": 2, "members": [5, 4]}
    for s in got:
        assert 0 < s.start <= s.end
    assert outer.start <= inner.start and inner.end <= second.start
    assert second.end <= outer.end
    own = (outer.end - outer.start) - sum(s.end - s.start
                                          for s in (inner, second))
    assert own >= 0.02e9 and inner.end - inner.start >= 0.01e9
    spans.reset()
    assert spans.timeline() == []


def test_a_profiler_session_switches_recording_on(tmp_path):
    import jax
    from jax.profiler import ProfileData
    spans.reset()
    jax.profiler.start_trace(str(tmp_path / "first"))
    try:
        with spans.span("simulate.setup", n=1):
            with spans.span("executor.sync"):
                pass
    finally:
        jax.profiler.stop_trace()
    with spans.span("executor.sync"):       # the session is over
        pass
    try:
        assert [s.name for s in spans.timeline()] == ["simulate.setup",
                                                      "executor.sync"]
        files = glob.glob(f"{tmp_path}/first/**/*.xplane.pb", recursive=True)
        assert len(files) == 1
        names = {e.name for p in ProfileData.from_file(files[0]).planes
                 for line in p.lines for e in line.events}
        assert {"archipelago.simulate.setup",
                "archipelago.executor.sync"} <= names
        # a new session starts the timeline afresh
        jax.profiler.start_trace(str(tmp_path / "second"))
        try:
            with spans.span("simulate.events"):
                pass
        finally:
            jax.profiler.stop_trace()
        assert [s.name for s in spans.timeline()] == ["simulate.events"]
    finally:
        spans.reset()


SMALL = ClusterConfig(n_sgs=2, workers_per_sgs=2, cores_per_worker=4,
                      pool_mem_mb=2048.0)


def _cmp_dict(res):
    d = res.to_dict()
    d.pop("wall_s")
    return d


def test_recording_changes_no_decision():
    exp = Experiment(workload_factory="paper_workload_1",
                     workload_kwargs=dict(duration=3.0, scale=0.02,
                                          dags_per_class=1),
                     cluster=SMALL, warmup=1.0, drain=3.0,
                     backend="stub-batched")
    off = _cmp_dict(simulate(exp))
    spans.reset()
    spans.force(True)
    try:
        on = _cmp_dict(simulate(exp))
        names = {s.name for s in spans.timeline()}
    finally:
        spans.force(False)
        spans.reset()
    assert on == off
    # the scheduler's per-request and periodic paths record nothing: their
    # time is the event loop's own
    assert names == {"simulate.setup", "simulate.events", "simulate.result",
                     "simulate.summary"}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_equivalence_goldens_hold_while_recording(name, recording):
    golden = json.loads(GOLDEN.read_text())[name]
    got = fingerprint_one(name)
    assert {k: got[k] for k in golden} == golden
    assert spans.timeline()


def test_jax_batched_run_batch_spans_lie_inside_the_event_loop(recording):
    from repro.core import BatchedJaxBackend
    from repro.serving import smoke_apps
    apps = smoke_apps()
    gen_len = next(iter(apps[0].models.values())).gen_len
    base = Experiment(stack="archipelago", workload_factory="serving_apps",
                      workload_kwargs=dict(apps=apps, duration=1.0,
                                           rps=8.0, prewarm_per_fn=2),
                      cluster=SMALL, warmup=0.2, drain=30.0)
    be = BatchedJaxBackend(max_batch=2, batching="windowed")
    spans.force(False)
    simulate(replace(base, backend=be))          # calibrates, unrecorded
    assert spans.timeline() == []
    spans.force(True)
    res = simulate(replace(base, backend=be))
    recs = spans.timeline()
    runs = [s for s in recs if s.name == "executor.run_batch"]
    assert res.backend_counters["n_batches"] == len(runs) > 0
    for r in runs:
        p = r.parent
        while p is not None and p.name != "simulate.events":
            p = p.parent
        assert p is not None
        assert p.start <= r.start <= r.end <= p.end
        assert [s.name for s in recs if s.parent is r] == (
            ["executor.prefill"] + ["executor.decode_step"] * gen_len
            + ["executor.sync"])
        assert r.attrs["n"] == len(r.attrs["members"])
