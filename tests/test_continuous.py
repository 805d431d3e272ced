"""Continuous batching (PR 9): ContinuousBatcher join/leave semantics and
determinism, the scripted stub twin under every stack, the
order-independent batch seed, and a tiny real-JAX continuous run."""
import json

import pytest

from repro.core import ClusterConfig, ContinuousBatcher, StubBatchedBackend
from repro.core.types import DagSpec, FunctionSpec, Invocation, Request
from repro.sim import Experiment, ExperimentResult, simulate
from repro.sim.engine import SimEnv

SMALL = ClusterConfig(n_sgs=2, workers_per_sgs=2, cores_per_worker=4,
                      pool_mem_mb=2048.0)


def _inv(fn_name="f", exec_time=0.1):
    dag = DagSpec("d", (FunctionSpec(fn_name, exec_time),), ())
    req = Request(dag=dag, arrival_time=0.0)
    return Invocation(request=req, fn=dag.fn(fn_name), ready_time=0.0)


def _batcher(env, admit_s=0.04, step_s=0.01, steps=3, max_batch=4):
    trace = []

    def admit(fn, invs, slots):
        trace.append(("admit", fn, [i.inv_id for i in invs], list(slots)))
        return admit_s

    def step(fn, slots):
        trace.append(("step", fn, list(slots)))
        return step_s

    cb = ContinuousBatcher(env, admit, step, lambda fn: steps,
                           max_batch=max_batch)
    return cb, trace


# -- ContinuousBatcher unit semantics ----------------------------------------


def test_same_instant_submits_join_one_prefill_in_inv_id_order():
    env = SimEnv()
    cb, trace = _batcher(env)
    done = []
    invs = [_inv() for _ in range(3)]
    # submit in REVERSE inv_id order: admission must re-sort
    for inv in reversed(invs):
        cb.submit(inv, lambda s, i=inv: done.append((env.now(), i.inv_id, s)))
    env.run()
    admits = [e for e in trace if e[0] == "admit"]
    assert admits == [("admit", "f", [i.inv_id for i in invs], [0, 1, 2])]
    # 3 joiners x 3 steps: ticks at 0, .05, .06; all leave at .07
    assert [e for e in trace if e[0] == "step"] \
        == [("step", "f", [0, 1, 2])] * 3
    assert done == [(pytest.approx(0.07), i.inv_id, pytest.approx(0.07))
                    for i in invs]
    assert cb.counters() == {"n_prefill_batches": 1, "n_joins": 3,
                             "n_decode_ticks": 3, "n_step_slots": 9,
                             "max_batch_occupancy": 3,
                             "n_dropped_invocations": 0}


def test_late_arrival_joins_running_batch_and_leaves_independently():
    env = SimEnv()
    cb, trace = _batcher(env, admit_s=0.04, step_s=0.01, steps=3)
    done = []
    a, b = _inv(), _inv()
    cb.submit(a, lambda s: done.append(("a", env.now())))
    # arrives mid-generation: joins at the next tick boundary, decodes
    # alongside a, finishes its own 3 steps later
    env.call_after(0.055, lambda: cb.submit(
        b, lambda s: done.append(("b", env.now()))))
    env.run()
    admits = [e for e in trace if e[0] == "admit"]
    assert len(admits) == 2 and admits[1][3] == [1]    # b gets slot 1
    # ticks: t=0 (admit a + step), t=.05 (step), t=.06 (admit b + step —
    # a's LAST step shares the tick with b's prefill, so a completes at
    # .06 + .04 + .01 = .11); b then steps alone at .11 and .12 -> .13
    assert done[0] == ("a", pytest.approx(0.11))
    assert done[1] == ("b", pytest.approx(0.13))
    # the shared tick ran both slots
    assert ("step", "f", [0, 1]) in trace


def test_freed_slot_is_reused_by_the_next_joiner():
    env = SimEnv()
    cb, trace = _batcher(env, steps=1, max_batch=2)
    invs = [_inv() for _ in range(4)]
    for inv in invs:
        cb.submit(inv, lambda s: None)
    env.run()
    admits = [e for e in trace if e[0] == "admit"]
    # capacity 2: two waves of two, each reusing slots {0,1}
    assert [a[2] for a in admits] == [[invs[0].inv_id, invs[1].inv_id],
                                      [invs[2].inv_id, invs[3].inv_id]]
    assert [a[3] for a in admits] == [[0, 1], [0, 1]]


def test_cold_delay_defers_enrollment():
    env = SimEnv()
    cb, trace = _batcher(env, steps=1)
    warm, cold = _inv(), _inv()
    cb.submit(warm, lambda s: None)
    cb.submit(cold, lambda s: None, 0.5)     # sandbox setup: joins at 0.5
    env.run()
    admits = [e for e in trace if e[0] == "admit"]
    assert [a[2] for a in admits] == [[warm.inv_id], [cold.inv_id]]


def test_zero_step_requests_complete_at_admission():
    env = SimEnv()
    cb, _ = _batcher(env, admit_s=0.04, steps=0)
    done = []
    cb.submit(_inv(), lambda s: done.append(env.now()))
    env.run()
    assert done == [pytest.approx(0.04)]


def test_batcher_validates_max_batch():
    with pytest.raises(ValueError, match="max_batch"):
        ContinuousBatcher(SimEnv(), lambda f, i, s: 0.0, lambda f, s: 0.0,
                          lambda f: 1, max_batch=0)


# -- the stub twin under the experiment API ----------------------------------


def _stub_exp(stack="archipelago", **kw):
    base = dict(stack=stack, backend="stub-batched",
                backend_kwargs=dict(exec_time=0.02, batching="continuous",
                                    max_batch=4, n_steps=3),
                workload_factory="paper_workload_1",
                workload_kwargs=dict(duration=3.0, scale=0.02,
                                     dags_per_class=1),
                cluster=SMALL, warmup=1.0, drain=3.0)
    base.update(kw)
    return Experiment(**base)


def test_stub_continuous_runs_under_every_stack_and_is_reproducible():
    from repro.core import available_stacks
    for name in available_stacks():
        a = simulate(_stub_exp(stack=name))
        assert a.n_completed > 0
        assert a.data_plane == {"kernels": "none", "batching": "continuous"}
        assert a.backend_counters["n_joins"] > 0
        assert a.backend_counters["n_decode_ticks"] > 0
        b = simulate(_stub_exp(stack=name))
        da, db = a.to_dict(), b.to_dict()
        da.pop("wall_s"), db.pop("wall_s")
        assert da == db, f"continuous run not reproducible under {name!r}"


def test_stub_continuous_counters_round_trip_through_json():
    res = simulate(_stub_exp())
    back = ExperimentResult.from_dict(json.loads(json.dumps(res.to_dict())))
    assert back.backend_counters == res.backend_counters
    assert back.data_plane == res.data_plane


def test_stub_lone_request_costs_exec_time_under_both_disciplines():
    """The scripted continuous twin splits exec_time into prefill + steps;
    an uncontended request must still take exactly exec_time end to end, so
    windowed and continuous stub latencies are directly comparable."""
    rows = {}
    for batching in ("windowed", "continuous"):
        # batch_window=0 so an uncontended windowed request flushes
        # immediately (no window wait to skew the comparison)
        exp = _stub_exp(backend_kwargs=dict(
            exec_time=0.02, batching=batching, max_batch=4, n_steps=3,
            batch_window=0.0),
            workload_kwargs=dict(duration=2.0, scale=0.002,
                                 dags_per_class=1))
        rows[batching] = simulate(exp)
    for r in rows.values():
        assert r.n_completed == r.n_requests
    assert rows["continuous"].latency_percentiles["p50"] == pytest.approx(
        rows["windowed"].latency_percentiles["p50"], rel=1e-6)


def test_stub_batched_validates_batching_choice():
    with pytest.raises(ValueError, match="batching"):
        StubBatchedBackend(batching="dynamic")


# -- order-independent batch seed --------------------------------------------


def test_batch_seed_is_order_independent_and_set_sensitive():
    jax = pytest.importorskip("jax")  # noqa: F841  (executor imports jax)
    from repro.serving.executor import batch_seed
    assert batch_seed([3, 1, 2]) == batch_seed([2, 3, 1])
    assert batch_seed([1]) != batch_seed([2])
    assert batch_seed([1, 2]) != batch_seed([1, 3])


def test_run_batch_seed_ignores_coalescing_order():
    """Regression: run_batch seeded from invs[0].inv_id made the executed
    work depend on gather order.  The member SET must determine the seed."""
    pytest.importorskip("jax")
    from repro.serving.executor import BatchingJaxExecutor, batch_seed

    class _FakeInstance:
        def __init__(self):
            self.seeds = []

        def run(self, seed=0):
            self.seeds.append(seed)
            return 0.001

    ex = BatchingJaxExecutor({}, max_batch=4)
    fake = _FakeInstance()
    ex._instances[("f", 4)] = fake
    invs = [_inv("f") for _ in range(3)]
    ex.run_batch("f", invs)
    ex.run_batch("f", list(reversed(invs)))
    assert fake.seeds[0] == fake.seeds[1] \
        == batch_seed(i.inv_id for i in invs)


def test_batch_buckets_share_one_weight_copy():
    """One weight copy per function: every bucket holds the same ``params``
    object, equal to what a bucket drawing its own weights gets (the same
    ``PRNGKey(0)`` init), so sharing changes no result."""
    pytest.importorskip("jax")
    import jax
    import numpy as np
    from repro.serving import smoke_apps
    from repro.serving.executor import BatchingJaxExecutor, ModelInstance

    served = dict(smoke_apps()[0].models)
    name = next(iter(served))
    ex = BatchingJaxExecutor(served, max_batch=2)
    insts = [ex.ensure_instance(name, b) for b in ex.buckets()]
    assert len(insts) == 2
    assert all(i.params is insts[0].params for i in insts)
    own = ModelInstance(insts[-1].served)
    own.setup()
    assert own.params is not insts[-1].params
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(np.array_equal(a, b)), own.params,
        insts[-1].params)))


# -- real JAX continuous serving ---------------------------------------------


def test_jax_continuous_serves_a_tiny_app_end_to_end():
    pytest.importorskip("jax")
    from dataclasses import replace
    from repro.core import BatchedJaxBackend
    from repro.serving import smoke_apps

    base = Experiment(
        stack="archipelago",
        workload_factory="serving_apps",
        workload_kwargs=dict(apps=smoke_apps(), duration=1.0, rps=4.0,
                             prewarm_per_fn=2),
        cluster=SMALL, warmup=0.2, drain=120.0)
    be = BatchedJaxBackend(max_batch=4, batching="continuous")
    res = simulate(replace(base, backend=be))
    assert res.n_completed == res.n_requests > 0
    assert res.data_plane == {"kernels": "xla", "batching": "continuous"}
    assert res.backend_counters["n_joins"] >= res.n_requests
    assert res.backend_counters["n_decode_ticks"] > 0
