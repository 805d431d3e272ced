"""Process-parallel sweeps (PR 5): ``run_sweep(workers=N)`` must produce
rows byte-identical to sequential execution, and the xl-tier workload
generation must be deterministic at 2,000-worker scale."""
import hashlib
import json
import warnings

import numpy as np
import pytest

from repro.sim import Experiment, run_sweep
from repro.sim.workload import paper_workload_1, paper_workload_2


def _canonical(rows):
    """JSON bytes of sweep rows with the one wall-clock timing field
    (``wall_s``) normalized — everything else must match bit-for-bit."""
    out = []
    for r in rows:
        d = json.loads(json.dumps(r))       # deep copy via the JSON round-trip
        d["result"]["wall_s"] = 0.0
        out.append(d)
    return json.dumps(out, sort_keys=True)


def _grid_base():
    return Experiment(
        workload_factory="paper_workload_1",
        workload_kwargs=dict(duration=2.0, scale=0.04, dags_per_class=1),
        warmup=0.5, drain=3.0)


def test_parallel_rows_byte_identical_to_sequential():
    """Mixed stack × backend × seed grid: a spawn-pool run returns the same
    deterministic cartesian-ordered rows as the sequential loop."""
    base = _grid_base()
    axes = {
        "stack": ["archipelago", "fifo"],
        "backend": ["modeled", "stub"],
        "seed": [0, 3],
    }
    seq = run_sweep(base, axes, workers=1)
    par = run_sweep(base, axes, workers=4)
    assert [r["cell"] for r in par.rows] == [r["cell"] for r in seq.rows]
    assert _canonical(par.rows) == _canonical(seq.rows)


def test_parallel_falls_back_on_unpicklable_cells():
    """A base experiment carrying live objects (here: a lambda workload
    factory) cannot cross a spawn boundary — run_sweep warns and runs
    sequentially instead of failing."""
    base = Experiment(workload_factory=lambda **kw: paper_workload_1(
        duration=1.0, scale=0.02, dags_per_class=1))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sweep = run_sweep(base, {"seed": [0, 1]}, workers=2)
    assert len(sweep.rows) == 2
    assert any("picklable" in str(w.message) for w in caught)
    # the warning must name WHICH field blocks pickling (the fix — a named
    # factory — should be obvious from the message alone)
    msg = next(str(w.message) for w in caught if "picklable" in str(w.message))
    assert "workload_factory" in msg
    assert "sequential" in msg


@pytest.mark.parametrize("shared_instance", [False, True])
def test_jax_data_plane_cells_run_in_process(monkeypatch, shared_instance):
    """Only one process can hold a chip: cells whose backend is a JAX data
    plane (named or an instance) run in the calling process with a
    warning, and no process pool is started."""
    import multiprocessing
    from repro.core import ClusterConfig, JaxBackend
    from repro.serving import smoke_apps

    def no_pool(*a, **kw):
        raise AssertionError("a JAX data-plane cell reached a process pool")

    monkeypatch.setattr(multiprocessing, "get_context", no_pool)
    base = Experiment(
        backend=JaxBackend() if shared_instance else "jax",
        workload_factory="serving_apps",
        workload_kwargs=dict(apps=smoke_apps(), duration=0.5, rps=4.0,
                             prewarm_per_fn=1),
        cluster=ClusterConfig(n_sgs=1, workers_per_sgs=2,
                              cores_per_worker=2),
        drain=30.0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sweep = run_sweep(base, {"seed": [0, 1]}, workers=2)
    assert [r["result"]["backend"] for r in sweep.rows] == ["jax", "jax"]
    assert all(r["result"]["n_completed"] == r["result"]["n_requests"] > 0
               for r in sweep.rows)
    msgs = [str(w.message) for w in caught]
    assert any("JAX data-plane" in m and "sequential" in m for m in msgs)


def test_keep_sim_runs_sequentially_and_keeps_handles():
    base = _grid_base()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sweep = run_sweep(base, {"seed": [0, 1]}, keep_sim=True, workers=4)
    assert sweep.experiment_results is not None
    assert all(r.sim is not None for r in sweep.experiment_results)
    # the sequential fallback must say WHY (keep_sim, not pickling)
    msgs = [str(w.message) for w in caught]
    assert any("keep_sim" in m and "sequential" in m for m in msgs)


def test_keep_sim_without_pool_request_does_not_warn():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        run_sweep(_grid_base(), {"seed": [0]}, keep_sim=True)
    assert not [w for w in caught if "keep_sim" in str(w.message)]


def test_detach_sim_is_explicit_and_keeps_serializability():
    base = _grid_base()
    sweep = run_sweep(base, {"seed": [0]})
    # keep_sim=False cells are detached: the row dict is the single source
    # and must JSON round-trip losslessly
    row = sweep.rows[0]["result"]
    assert json.loads(json.dumps(row)) == row
    from repro.sim.experiment import ExperimentResult
    rt = ExperimentResult.from_dict(row)
    assert rt.sim is None
    assert rt.to_dict() == row


# ---------------------------------------------------------------------------
# xl-tier workload determinism (2,000-worker scale: 80 tenants, ~1 M+
# arrivals at the full benchmark settings; the test trims duration so it
# stays seconds-fast while exercising the same tenant fan-out)
# ---------------------------------------------------------------------------


def _xl_hash(factory, seed):
    spec = factory(duration=6.0, scale=10.0, dags_per_class=20)
    ts, idx, dags = spec.generate_arrays(seed)
    assert len(dags) == 80                      # 4 classes x 20 tenants
    h = hashlib.sha256()
    h.update(ts.tobytes())
    h.update(idx.astype(np.int64).tobytes())
    h.update("|".join(d.dag_id for d in dags).encode())
    return len(ts), h.hexdigest()


@pytest.mark.parametrize("factory", [paper_workload_1, paper_workload_2])
def test_xl_workload_generation_deterministic(factory):
    n1, h1 = _xl_hash(factory, seed=0)
    n2, h2 = _xl_hash(factory, seed=0)
    assert (n1, h1) == (n2, h2)
    # ~26k rps aggregate: the 6 s slice alone is ~150k arrivals, scaling to
    # >= 1 M at the benchmark's 40 s duration
    assert n1 > 100_000
    # different seed -> different trace (no accidental seed pinning)
    _, h3 = _xl_hash(factory, seed=1)
    assert h3 != h1


def test_xl_workload_generation_deterministic_across_processes():
    """The xl trace must not depend on process state (hash salts etc.):
    regenerate in a spawned child and compare hashes."""
    import multiprocessing
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(1) as pool:
        child = pool.apply(_xl_hash, (paper_workload_1, 0))
    assert child == _xl_hash(paper_workload_1, 0)
