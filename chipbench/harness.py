"""One benchmark run: set-up, the measured window of ``simulate`` segments,
the per-layer readings, and the check that decides ``correct``.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``configs/<config>.json``, its traffic mix in
``traffic/<traffic>.json``, the architecture's benchmark side in
``arch/<model_type>.py`` and each per-layer metric's reader in
``metrics/<metric>.py``.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import random
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import yardstick                    # noqa: E402
from arch import common             # noqa: E402

SPAN_PREFIX = "chipbench."


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_module(cfg: Dict[str, Any]):
    """The architecture's benchmark side, ``arch/<model_type>.py``."""
    return importlib.import_module(f"arch.{cfg['model_type']}")


def load_cell(name: str, root: Path = ROOT) -> Dict[str, Any]:
    """The cell ``name`` with its configuration, traffic mix and metric
    entries, all read from files under ``root``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((root / HERE.name / "traffic"
                          / f"{cell['traffic']}.json").read_text())

    def applies(m):
        return name in m.get("workloads", [name])

    return dict(cell=cell, config=cfg, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


# ---------------------------------------------------------------------------
# Host spans and the executor's recorded outputs
# ---------------------------------------------------------------------------


@dataclass
class Batch:
    """One call of the executor's ``run_batch``: its members, its wall
    span, and the tokens the program produced (kept on the device)."""

    n: int
    bucket: int
    t0: float
    t1: float = 0.0
    traced: bool = False
    prompt: Any = None
    fed: List[Any] = field(default_factory=list)


class Recorder:
    """Wraps the executor's ``run_batch`` and each bucket's prefill and
    decode programs (``ModelInstance.prefill_fn`` and ``decode_fn``, got
    through ``ensure_instance``) from outside: times every device call, and
    keeps the prompt and every token fed back, as the program produced
    them.  This is the one place the benchmark reads the program's tokens:
    ``ModelInstance.run`` returns only its wall time, so a program that
    stops calling ``decode_fn`` once per token leaves ``fed`` short, and
    ``missing_rows`` then reads the batch as not served.

    ``fault`` plants a fault under the timed path (tests and control runs):
    ``"token"`` alters one produced token of every batch, ``"stale_cache"``
    has each decode step return the cache it was given, ``"drop"`` computes
    only the first half of every batch of two or more."""

    def __init__(self, executor, fault: str = ""):
        self.trace = False              # the profiler is recording
        self.fault = fault
        self.batches: List[Batch] = []
        self.current: Optional[Batch] = None
        self.recording = False
        self._orig_run_batch = executor.run_batch
        executor.run_batch = self.run_batch
        for fn in executor.served:
            for b in executor.buckets():
                self._wrap(executor.ensure_instance(fn, b))

    def _wrap(self, inst) -> None:
        prefill, decode = inst.prefill_fn, inst.decode_fn
        rec = self

        def prefill_fn(params, tokens, cache, *rest):
            if rec.current is not None:
                rec.current.prompt = tokens
            return prefill(params, tokens, cache, *rest)

        def decode_fn(params, cache, tok, t):
            cur = rec.current
            if cur is not None:
                if rec.fault == "token" and not cur.fed:
                    tok = tok.at[0, 0].set((tok[0, 0] + 1) % 7)
                cur.fed.append(tok)
            logits, new_cache = decode(params, cache, tok, t)
            if rec.fault == "stale_cache":
                new_cache = cache
            return logits, new_cache

        inst.prefill_fn, inst.decode_fn = prefill_fn, decode_fn

    def run_batch(self, fn_name: str, invs) -> float:
        b = Batch(n=len(invs), bucket=1 << (len(invs) - 1).bit_length(),
                  t0=time.perf_counter(), traced=self.trace)
        if self.fault == "drop" and len(invs) > 1:
            invs = invs[:(len(invs) + 1) // 2]
        self.current = b if self.recording else None
        if self.trace:
            import jax
            with jax.profiler.TraceAnnotation(
                    f"{SPAN_PREFIX}device_call", bucket=b.bucket):
                dt = self._orig_run_batch(fn_name, invs)
        else:
            dt = self._orig_run_batch(fn_name, invs)
        b.t1 = time.perf_counter()
        self.current = None
        if self.recording:
            self.batches.append(b)
        return dt


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache."""

    def __init__(self):
        import jax
        self.n = 0
        self.on = False

        def listener(event, duration, **kw):
            if self.on and event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


def derived_seed(seed: int, *salt: int) -> int:
    """A 62-bit seed derived from the run's seed and a salt."""
    rng = random.Random(f"{seed}:{':'.join(map(str, salt))}")
    return rng.getrandbits(62)


def arrival_process(traffic: Dict[str, Any]):
    """The traffic file's arrival process over one segment, in simulated
    seconds from the segment's start (``sim/workload.py``'s processes)."""
    from repro.sim.workload import BurstRate, ConstantRate, WindowedRate
    a = traffic["arrivals"]
    start = float(traffic["warmup_s"])
    seg = float(traffic["segment_s"])
    proc = ConstantRate(float(a["rps"]))
    burst = a.get("burst")
    if burst:
        dur = burst["share"] * seg
        proc = BurstRate(proc, at=start + burst["at_share"] * seg,
                         duration=dur, amplify=float(burst["amplify"]),
                         ramp=burst["ramp_share"] * dur)
    return WindowedRate(proc, start=start, end=start + seg)


def build_program(bundle: Dict[str, Any], kernels: str):
    """The program's model config, served model and serving app."""
    from repro.models.config import ModelConfig
    from repro.serving import ServedModel, ServingApp
    cfg_json, traffic = bundle["config"], bundle["traffic"]
    arch = arch_module(cfg_json)
    mcfg = ModelConfig(**arch.program_config(cfg_json), kernels=kernels)
    served = ServedModel(mcfg, prompt_len=traffic["prompt_len"],
                         gen_len=traffic["gen_len"])
    dag = bundle["cell"]["name"]
    app = ServingApp(dag, {f"{dag}/generate": served},
                     slack=float(traffic["slack_s"]))
    return arch, mcfg, served, app


def make_weights(bundle, arch, mcfg, seed: int, program: bool):
    """The benchmark's weights for the run's seed, in the program's tree
    (its shapes and dtypes, taken without values from ``init_params``):
    the published values for the reference, or with ``program`` those
    values in the program's layout."""
    import jax
    from repro.models import init_params
    specs = jax.eval_shape(lambda: init_params(mcfg, jax.random.PRNGKey(0)))
    return common.make_weights(bundle["config"], arch, specs,
                               derived_seed(seed, 0), program=program)


def setup_backend(bundle, seed: int, kernels: str):
    """One ``jax-batched`` backend instance with the benchmark's weights,
    calibrated: every bucket of the cell compiles (or loads) here."""
    from repro.core.backends import BatchedJaxBackend, served_model_key
    from repro.serving.executor import BatchingJaxExecutor
    traffic = bundle["traffic"]
    arch, mcfg, served, app = build_program(bundle, kernels)
    fn = next(iter(app.models))
    weights = make_weights(bundle, arch, mcfg, seed, program=True)
    backend = BatchedJaxBackend(
        kernels=kernels, batching="windowed",
        max_batch=int(traffic["max_batch"]),
        batch_window=float(traffic["batch_window_s"]))
    ex = BatchingJaxExecutor({fn: served}, max_batch=backend.max_batch)
    # the program draws its own weights when none are set: give it the
    # benchmark's, so that the reference can draw the same from the seed
    ex._params[fn] = weights
    backend.fn_specs = ex.calibrate(mem_mb=backend.mem_mb,
                                    runs=backend.calib_runs)
    backend.executor = ex
    backend._calibrated_key = served_model_key({fn: served})
    return backend, app, arch, mcfg


@dataclass
class Segment:
    attempted: int
    completed: int
    met: int
    cold_starts: int
    latencies: List[float]
    lost: int
    duplicates: int
    pending: int
    counters: Dict[str, int]
    t0: float
    t1: float
    halves_p95: tuple = ()      # p95 of arrivals in each half of the segment


def run_segment(bundle, backend, app, index: int) -> Segment:
    """Segment ``index`` of the cell's arrival schedule.  The schedule is
    fixed by the traffic file's ``arrival_seed``, the same for every run
    seed: re-drawing Poisson arrivals per seed changed the work (phi3-chat,
    30 s windows: p95 spread 34% of the median between seeds, 0.1% between
    two runs of one seed)."""
    from repro.core import ClusterConfig
    from repro.sim import Experiment, simulate
    traffic = bundle["traffic"]
    c = traffic["cluster"]
    warmup = float(traffic["warmup_s"])
    seg_s = float(traffic["segment_s"])
    specs = getattr(backend, "fn_specs", None) or {}
    spec_setup = max((s.setup_time for s in specs.values()), default=0.0)
    if spec_setup >= warmup:
        raise RuntimeError(f"measured sandbox set-up {spec_setup:.1f} s is "
                           f"not below the segment's warm-up {warmup} s")
    t0 = time.perf_counter()
    exp = Experiment(
        stack="archipelago", backend=backend,
        workload_factory="serving_apps",
        workload_kwargs=dict(
            apps=[app], duration=warmup + seg_s,
            prewarm_per_fn=int(traffic["prewarm_per_fn"]),
            arrivals={app.dag_id: arrival_process(traffic)}),
        cluster=ClusterConfig(n_sgs=c["n_sgs"],
                              workers_per_sgs=c["workers_per_sgs"],
                              cores_per_worker=c["cores_per_worker"]),
        warmup=warmup, drain=float(traffic["drain_s"]),
        seed=derived_seed(int(traffic["arrival_seed"]), 1, index))
    res = simulate(exp)
    m = res.sim.metrics.after_warmup(warmup)
    lat = [float(x) for x in m.latencies()]
    mid, end = warmup + seg_s / 2, warmup + seg_s
    halves = tuple(_p95([float(x) for x in m.window(a, b).latencies()])
                   for a, b in ((warmup, mid), (mid, end)))
    met = round((res.deadline_met_frac or 0.0) * res.n_requests)
    acc = res.accounting
    seg = Segment(attempted=res.n_requests, completed=res.n_completed,
                  met=met, cold_starts=res.cold_start_count, latencies=lat,
                  lost=acc.get("lost", 0),
                  duplicates=acc.get("duplicate_completions", 0),
                  pending=acc.get("pending", 0),
                  counters=dict(res.backend_counters), t0=t0,
                  t1=time.perf_counter(), halves_p95=halves)
    return seg


def _p95(lat: List[float]) -> float:
    return yardstick.percentile(lat, 95.0) if lat else math.nan


TRACE_SECONDS = 5.0


def window(bundle, backend, app, seconds: float, trace_dir: Optional[Path],
           recorder: Recorder, counter: CompileCounter):
    """Back-to-back segments until ``seconds`` of wall time have passed.
    With ``trace_dir``, the profiler records the segments that start in
    the window's first ``TRACE_SECONDS`` (a whole window of a small model
    is millions of device events).  Returns the segments, the window's
    start and end, and the seconds spent stopping the profiler inside it
    (seconds of host work that serve no request)."""
    import jax
    segs: List[Segment] = []
    tracing = trace_dir is not None
    if tracing:
        jax.profiler.start_trace(str(trace_dir))
    counter.on = True
    recorder.recording = True
    stopping = 0.0
    t0 = time.perf_counter()
    while True:
        recorder.trace = tracing
        if tracing:
            with jax.profiler.TraceAnnotation(f"{SPAN_PREFIX}segment"):
                segs.append(run_segment(bundle, backend, app, len(segs)))
            if time.perf_counter() - t0 >= TRACE_SECONDS:
                t = time.perf_counter()
                jax.profiler.stop_trace()
                stopping = time.perf_counter() - t
                tracing = False
        else:
            segs.append(run_segment(bundle, backend, app, len(segs)))
        if time.perf_counter() - t0 >= seconds:
            break
    t1 = time.perf_counter()
    recorder.recording = recorder.trace = False
    counter.on = False
    if tracing:
        jax.profiler.stop_trace()
    return segs, t0, t1, stopping


def end_to_end(segs: List[Segment], window_s: float) -> Dict[str, float]:
    attempted = sum(s.attempted for s in segs)
    completed = sum(s.completed for s in segs)
    lat = [x for s in segs for x in s.latencies]
    lat += [math.inf] * (attempted - len(lat))
    return {"req_per_s": completed / window_s,
            "latency_p95_ms": yardstick.percentile(lat, 95.0) * 1e3,
            "deadline_met_pct": 100.0 * sum(s.met for s in segs) / attempted}
