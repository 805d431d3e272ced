"""The harness without a chip: files found by name, the segment loop,
pooled end-to-end numbers, and the refusal to run off a TPU."""
import json
import math
import shutil
from types import SimpleNamespace

import pytest

import chipbench_tiny
from chipbench_tiny import BENCH, ROOT
import harness
import run


def test_every_entry_is_found_by_file_name():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        b = harness.load_cell(cell["name"])
        assert b["traffic"] == json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
        assert harness.arch_module(b["config"]).logits
        for m in b["per_layer"]:
            assert harness.load_module(
                BENCH / "metrics" / f"{m['name']}.py").read
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A configuration, a traffic mix and a per-layer metric are added by
    adding files and entries; no file already there changes."""
    shutil.copytree(BENCH, tmp_path / BENCH.name,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (tmp_path / BENCH.name).rglob("*")
              if p.is_file()}
    cfg = json.loads((BENCH / "configs" / "phi3-mini-3.8b.json").read_text())
    cfg.update(name="phi3-two-layers", num_hidden_layers=2)
    (tmp_path / BENCH.name / "configs" / "phi3-two-layers.json").write_text(
        json.dumps(cfg))
    tr = json.loads((BENCH / "traffic" / "phi3-chat.json").read_text())
    tr["prompt_len"] = 1024
    (tmp_path / BENCH.name / "traffic" / "long.json").write_text(
        json.dumps(tr))
    (tmp_path / BENCH.name / "metrics" / "twice_occupancy.py").write_text(
        "def read(ctx):\n    return 2.0\n")
    bench["configs"].append(dict(bench["configs"][0], name="phi3-two-layers",
                                 file="chipbench/configs/phi3-two-layers.json"))
    bench["workloads"].append(dict(name="phi3-two.long",
                                   config="phi3-two-layers", traffic="long",
                                   chips=1, why="test"))
    bench["per_layer"].append(dict(bench["per_layer"][0],
                                   name="twice_occupancy",
                                   workloads=["phi3-two.long"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    b = harness.load_cell("phi3-two.long", root=tmp_path)
    assert b["config"]["num_hidden_layers"] == 2
    assert b["traffic"]["prompt_len"] == 1024
    assert [m["name"] for m in b["per_layer"]] == ["twice_occupancy"]
    reader = harness.load_module(
        tmp_path / BENCH.name / "metrics" / "twice_occupancy.py")
    assert reader.read({}) == 2.0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_segment_loop_on_the_stub_batched_backend():
    from repro.core.backends import StubBatchedBackend
    from repro.serving import ServedModel, ServingApp
    from repro.configs import get_config
    b = chipbench_tiny.bundle("phi3")
    b["traffic"].update(segment_s=2.0)
    app = ServingApp("tiny", {"tiny/generate": ServedModel(
        get_config("phi3-mini-3.8b", reduced=True))}, slack=1.0)
    backend = StubBatchedBackend(exec_time=0.02, setup_time=1.0,
                                 batch_window=0.05, max_batch=4)
    off = SimpleNamespace(on=False, recording=False)
    segs, t0, t1, stopping = harness.window(
        b, backend, app, seconds=0.2, trace_dir=None, recorder=off,
        counter=off)
    assert len(segs) >= 1 and t1 - t0 >= 0.2 and stopping == 0.0
    for s in segs:
        assert s.attempted > 0 and s.completed == s.attempted
        assert s.lost == s.duplicates == s.pending == 0
        assert s.counters["n_batched_invocations"] == s.attempted
        assert len(s.latencies) == s.completed
    # the cell's arrival schedule is the same in every run, segment by
    # segment; another arrival seed gives another schedule
    again = harness.window(b, backend, app, seconds=0.0, trace_dir=None,
                           recorder=off, counter=off)[0]
    assert again[0].attempted == segs[0].attempted
    assert again[0].latencies == segs[0].latencies
    b["traffic"]["arrival_seed"] += 1
    other = harness.run_segment(b, backend, app, 0)
    assert other.latencies != segs[0].latencies


def _seg(lat, attempted, met):
    return harness.Segment(attempted=attempted, completed=len(lat), met=met,
                           cold_starts=0, latencies=lat, lost=0,
                           duplicates=0, pending=0, counters={}, t0=0, t1=0)


def test_end_to_end_pools_every_request_of_every_segment():
    a = _seg([0.1] * 50, 50, 50)
    b = _seg([0.2] * 45, 50, 40)          # five never finished
    e = harness.end_to_end([a, b], window_s=4.0)
    assert e["req_per_s"] == pytest.approx(95 / 4.0)
    assert e["deadline_met_pct"] == pytest.approx(90.0)
    assert e["latency_p95_ms"] == pytest.approx(200.0)
    e = harness.end_to_end([a, _seg([0.2] * 44, 50, 40)], window_s=4.0)
    assert e["latency_p95_ms"] == math.inf


def test_no_tpu_exits_nonzero_and_names_the_platform(capsys):
    assert run.main(["--workload", "phi3-chat", "--seed", "1",
                     "--seconds", "1"]) == 2
    err = capsys.readouterr()
    assert "'cpu'" in err.err and err.out == ""


@pytest.mark.parametrize("burst", [None, dict(at_share=0.5, share=0.25,
                                              amplify=3.0, ramp_share=0.2)])
def test_arrival_process_follows_the_traffic_file(burst):
    """One generator reads every mix: a steady rate inside the segment's
    arrival window, nothing outside it, and a flash crowd where the file
    asks for one."""
    tr = dict(warmup_s=10.0, segment_s=8.0, arrivals={"rps": 4.0})
    if burst:
        tr["arrivals"]["burst"] = burst
    proc = harness.arrival_process(tr)
    assert proc.rate(5.0) == 0.0 and proc.rate(18.5) == 0.0
    assert proc.rate(11.0) == pytest.approx(4.0)
    peak = proc.rate(10.0 + 8.0 * 0.5 + 1.0)        # mid-burst
    assert peak == pytest.approx(12.0 if burst else 4.0)
