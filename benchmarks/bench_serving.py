"""Serving benchmark: hardware-in-the-loop ``ExperimentResult`` rows.

Sweeps the multitenant serving app set (dense / SSM / MoE / two-stage VLM
pipeline) over scheduler stacks through ``run_sweep``, with real JAX
execution (``backend="jax"``: one shared backend instance, so the models
calibrate/compile once across all cells) and writes a structured
``BENCH_serving.json``: full per-cell ``ExperimentResult`` rows plus a
flattened per-class view, and — on full (non-smoke) real-JAX runs — two
paired comparisons on identical traffic:

* **batched vs unbatched** (``jax-batched`` vs ``jax``): what window
  coalescing buys over one-model-run-per-invocation;
* **continuous vs windowed** (``batching="continuous"`` vs
  ``"windowed"`` under ``jax-batched``, decode-heavy app): what
  step-granular join/leave buys over request-window coalescing, with the
  measured per-bucket admit/step device times and an analytic TPU roofline
  anchor for the decode step.

    python -m benchmarks.bench_serving [--smoke] \
        [--backend jax|jax-batched|stub|stub-batched] \
        [--kernels xla|pallas|pallas_interpret] \
        [--batching windowed|continuous]

``--kernels`` / ``--batching`` pick the data plane for the main sweep; both
are recorded per row (``data_plane`` in each ``ExperimentResult``, plus
``kernels``/``batching`` columns in ``per_class_rows``).

``--smoke`` runs 1 small model for a short duration and writes
``BENCH_serving.partial.json`` (gitignored) so partial runs never clobber
the tracked artifact — the PR-2 ``--only`` convention.  ``--backend stub``
replays the same pipeline with deterministic scripted times (no compiles);
``stub-batched``/``jax-batched`` route execution through the batching data
plane (``BatchCoalescer``).

Throughput note: the simulator grants every invocation its own abstract
core, so *simulated* completion counts cannot show what batching buys on
one physical device.  The comparison therefore reports
``completed_per_wall_s`` — completed requests per wall-clock second of the
run, i.e. what the actual hardware sustained while the event loop drove it.
Per-invocation ``jax`` pays one full model run per invocation; ``jax-batched``
amortizes weight reads across every batch member, so the same request count
needs a fraction of the device time.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

from .common import timer  # noqa: F401  (also bootstraps sys.path for src/)

from repro.core import (BatchedJaxBackend, ClusterConfig, JaxBackend,
                        StubBackend, StubBatchedBackend)
from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import V5E, peak_rates
from repro.serving import (ServedModel, ServingApp, multitenant_apps,
                           smoke_apps)
from repro.sim import Experiment, run_sweep, simulate

STACKS = ["archipelago", "fifo", "pull"]

# batched-vs-unbatched comparison knobs: one small model, enough offered
# load that several invocations are in flight per batch window
COMPARE_RPS = 450.0
COMPARE_DURATION = 4.0
COMPARE_WINDOW = 0.008
COMPARE_MAX_BATCH = 8

# continuous-vs-windowed comparison knobs: a decode-heavy function (decode
# steps dominate prefill), offered load within the capacity of ONE
# serialized continuous device chain, arrivals staggered so request-window
# coalescing catches low occupancy while step-level batching stays full
CONT_RPS = 30.0
CONT_DURATION = 4.0
CONT_PROMPT = 16
CONT_GEN = 12


def _make_backend(name: str, batch_window: float = COMPARE_WINDOW,
                  max_batch: int = COMPARE_MAX_BATCH,
                  kernels: str = "xla", batching: str = "windowed"):
    if name == "jax":
        return JaxBackend(kernels=kernels)
    if name == "jax-batched":
        return BatchedJaxBackend(batch_window=batch_window,
                                 max_batch=max_batch,
                                 kernels=kernels, batching=batching)
    if name == "stub":
        return StubBackend(exec_time=0.020, setup_time=1.0)
    if name == "stub-batched":
        return StubBatchedBackend(exec_time=0.020, setup_time=1.0,
                                  batch_window=batch_window,
                                  max_batch=max_batch, batching=batching)
    raise ValueError(name)


def batched_comparison() -> dict:
    """``jax`` vs ``jax-batched`` on identical traffic: same app, same
    arrivals, same cluster — only the data plane differs.  Returns the
    comparison rows plus the headline wall-clock-throughput speedup."""
    apps = smoke_apps()
    base = Experiment(
        stack="archipelago",
        workload_factory="serving_apps",
        workload_kwargs=dict(apps=apps, duration=COMPARE_DURATION,
                             rps=COMPARE_RPS, prewarm_per_fn=4),
        cluster=ClusterConfig(n_sgs=2, workers_per_sgs=2,
                              cores_per_worker=4),
        warmup=1.0, drain=5.0)
    rows = {}
    for name in ("jax", "jax-batched"):
        print(f"[bench_serving] comparison: {name} @ {COMPARE_RPS:.0f} rps "
              f"(real executions)...", flush=True)
        res = simulate(replace(base, backend=_make_backend(name)))
        d = res.to_dict()
        # completed requests per wall second: what the hardware sustained
        d["completed_per_wall_s"] = (
            res.n_completed / res.wall_s if res.wall_s else None)
        rows[name] = d
        extra = ""
        bc = res.backend_counters
        if bc.get("n_batches"):
            extra = (f" batches={bc['n_batches']} "
                     f"mean_occ={bc['n_batched_invocations']/bc['n_batches']:.2f} "
                     f"max_occ={bc['max_batch_occupancy']}")
        print(f"  {name:>12}: done={res.n_completed} wall={res.wall_s:.1f}s "
              f"-> {d['completed_per_wall_s']:.1f} req/wall-s{extra}",
              flush=True)
    speedup = (rows["jax-batched"]["completed_per_wall_s"]
               / rows["jax"]["completed_per_wall_s"])
    print(f"  batched throughput speedup: {speedup:.2f}x", flush=True)
    return {
        "rps": COMPARE_RPS,
        "duration": COMPARE_DURATION,
        "batch_window": COMPARE_WINDOW,
        "max_batch": COMPARE_MAX_BATCH,
        "metric": "completed_per_wall_s (completed requests per wall-clock "
                  "second: real device throughput under the event loop)",
        "results": rows,
        "throughput_speedup": speedup,
    }


def _decode_roofline(cfg, max_batch: int) -> dict:
    """Analytic TPU-v5e bound on one decode step at each batch size.

    A decode step reads every active weight once (bf16: 2 bytes/param) and
    does ~2 FLOPs per active param per batch member, so small batches are
    HBM-bound: the step-time floor is flat in batch size until the compute
    term catches up.  That flat floor is exactly why continuous batching
    pays — B requests share one weight read per token."""
    n = cfg.active_param_count()
    weight_bytes = 2 * n
    peak = peak_rates(V5E)
    per_batch = {}
    b = 1
    while b <= max_batch:
        flops = 2 * n * b
        per_batch[b] = {
            "flops": flops,
            "hbm_bytes": weight_bytes,
            "bound_s": max(flops / peak.flops_bf16,
                           weight_bytes / peak.hbm_bw),
            "bound": ("hbm" if weight_bytes / peak.hbm_bw
                      >= flops / peak.flops_bf16 else "compute"),
        }
        b *= 2
    return {
        "model": "mamba2-370m (reduced)",
        "active_params": n,
        "device_kind": V5E,
        "peak_flops_bf16": peak.flops_bf16,
        "hbm_bw": peak.hbm_bw,
        "note": "per-decode-step lower bound: max(2*N*B/peak_flops, "
                "2*N/hbm_bw); reduced configs are far from saturating a "
                "v5e, so measured step times sit well above bound_s — the "
                "anchor shows the *shape* (flat until compute-bound), which "
                "the measured bucket_step_s medians reproduce",
        "per_batch": per_batch,
    }


def continuous_comparison() -> dict:
    """Windowed vs continuous batching on identical decode-heavy traffic
    (``gen_len`` decode steps dominate prefill).  Windowed coalescing only
    batches requests that arrive inside one window; continuous batching
    lets arrivals join the running batch at token-step boundaries, so the
    device stays occupied at high batch size.  Reports
    ``completed_per_wall_s`` plus the measured per-bucket device times and
    an analytic roofline anchor for the decode step."""
    app = ServingApp("decode", {"ssm/decode": ServedModel(
        get_config("mamba2-370m", reduced=True),
        prompt_len=CONT_PROMPT, gen_len=CONT_GEN)}, slack=2.0)
    base = Experiment(
        stack="archipelago",
        workload_factory="serving_apps",
        workload_kwargs=dict(apps=[app], duration=CONT_DURATION,
                             rps=CONT_RPS, prewarm_per_fn=4),
        cluster=ClusterConfig(n_sgs=2, workers_per_sgs=2,
                              cores_per_worker=4),
        warmup=1.0, drain=8.0)
    rows = {}
    device_times = {}
    for batching in ("windowed", "continuous"):
        print(f"[bench_serving] comparison: jax-batched/{batching} "
              f"@ {CONT_RPS:.0f} rps x {CONT_GEN} decode steps...",
              flush=True)
        be = _make_backend("jax-batched", batching=batching)
        res = simulate(replace(base, backend=be))
        d = res.to_dict()
        d["completed_per_wall_s"] = (
            res.n_completed / res.wall_s if res.wall_s else None)
        rows[batching] = d
        ex = be.executor
        if batching == "continuous":
            device_times["bucket_admit_s"] = {
                b: t for (_, b), t in sorted(ex.bucket_admit_s.items())}
            device_times["bucket_step_s"] = {
                b: t for (_, b), t in sorted(ex.bucket_step_s.items())}
        else:
            device_times["bucket_exec_s"] = {
                b: t for (_, b), t in sorted(ex.bucket_exec_s.items())}
        bc = res.backend_counters
        if batching == "continuous":
            extra = (f" ticks={bc['n_decode_ticks']} "
                     f"mean_step_occ="
                     f"{bc['n_step_slots']/bc['n_decode_ticks']:.2f} "
                     f"max_occ={bc['max_batch_occupancy']}")
        else:
            extra = (f" batches={bc['n_batches']} "
                     f"mean_occ={bc['n_batched_invocations']/bc['n_batches']:.2f} "
                     f"max_occ={bc['max_batch_occupancy']}")
        print(f"  {batching:>12}: done={res.n_completed} "
              f"wall={res.wall_s:.1f}s "
              f"-> {d['completed_per_wall_s']:.1f} req/wall-s{extra}",
              flush=True)
    speedup = (rows["continuous"]["completed_per_wall_s"]
               / rows["windowed"]["completed_per_wall_s"])
    print(f"  continuous throughput speedup: {speedup:.2f}x", flush=True)
    return {
        "rps": CONT_RPS,
        "duration": CONT_DURATION,
        "prompt_len": CONT_PROMPT,
        "gen_len": CONT_GEN,
        "batch_window": COMPARE_WINDOW,
        "max_batch": COMPARE_MAX_BATCH,
        "metric": "completed_per_wall_s (completed requests per wall-clock "
                  "second: real device throughput under the event loop)",
        "results": rows,
        "device_times": device_times,
        "roofline": _decode_roofline(get_config("mamba2-370m", reduced=True),
                                     COMPARE_MAX_BATCH),
        "throughput_speedup": speedup,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="1 small model, short duration, partial artifact")
    ap.add_argument("--backend", default="jax",
                    choices=["jax", "jax-batched", "stub", "stub-batched"])
    ap.add_argument("--no-compare", action="store_true",
                    help="skip the jax-batched vs jax comparison (it runs "
                         "on full --backend jax runs by default)")
    ap.add_argument("--batch-window", type=float, default=COMPARE_WINDOW,
                    help="batched backends, main sweep only: coalescing "
                         "window in sim seconds (the comparison always uses "
                         "the pinned COMPARE_* constants)")
    ap.add_argument("--max-batch", type=int, default=COMPARE_MAX_BATCH,
                    help="batched backends, main sweep only: size-triggered "
                         "flush threshold")
    ap.add_argument("--kernels", default="xla",
                    choices=["xla", "pallas", "pallas_interpret"],
                    help="jax backends, main sweep only: serving-model "
                         "hot-spot implementation (docs/KERNELS.md); "
                         "recorded per row in data_plane")
    ap.add_argument("--batching", default="windowed",
                    choices=["windowed", "continuous"],
                    help="batched backends, main sweep only: request-window "
                         "coalescing vs step-granular continuous batching "
                         "(docs/SERVING.md); recorded per row in data_plane")
    ap.add_argument("--workers", type=int, default=1,
                    help="run sweep cells in N worker processes "
                         "(repro.sim.run_sweep(workers=N)).  Works with "
                         "the stub backends; JAX backend cells run in this "
                         "process with a warning (one process per chip)")
    ap.add_argument("--out", default="",
                    help="JSON artifact path (default: BENCH_serving.json "
                         "at the repo root, or BENCH_serving.partial.json "
                         "with --smoke)")
    args = ap.parse_args()
    enable_compile_cache()

    apps = smoke_apps() if args.smoke else multitenant_apps()
    backend = _make_backend(args.backend, args.batch_window, args.max_batch,
                            kernels=args.kernels, batching=args.batching)
    if args.backend.startswith("jax"):
        # one instance shared across every sweep cell: calibrate once
        n_models = len({id(m) for a in apps for m in a.models.values()})
        per = ("one executable per batch bucket"
               if args.backend == "jax-batched" else "real XLA compiles")
        print(f"[bench_serving] calibrating {n_models} model(s) ({per})...",
              flush=True)

    duration = 3.0 if args.smoke else 12.0
    base = Experiment(
        backend=backend,
        workload_factory="serving_apps",
        workload_kwargs=dict(apps=apps, duration=duration, rps=6.0,
                             prewarm_per_fn=3),
        cluster=ClusterConfig(n_sgs=2 if args.smoke else 3,
                              workers_per_sgs=2, cores_per_worker=2),
        warmup=0.0 if args.smoke else 4.0,
        drain=10.0)
    stacks = STACKS[:2] if args.smoke else STACKS

    t0 = time.time()
    sweep = run_sweep(base, {"stack": stacks}, workers=args.workers)
    per_class_rows = []
    for row in sweep:
        res = row["result"]
        print(f"  {row['cell']['stack']:>12}: n={res['n_requests']} "
              f"done={res['n_completed']} "
              f"p99={(res['latency_percentiles']['p99'] or 0)*1e3:.1f}ms "
              f"deadlines_met={(res['deadline_met_frac'] or 0)*100:.1f}% "
              f"cold_starts={res['cold_start_count']} "
              f"batches={res['backend_counters'].get('n_batches', 0)}",
              flush=True)
        dp = res.get("data_plane", {})
        for cls, stats in sorted(res["per_class"].items()):
            per_class_rows.append(dict(stats, **row["cell"],
                                       dag_class=cls,
                                       backend=res["backend"],
                                       kernels=dp.get("kernels", "none"),
                                       batching=dp.get("batching", "none")))

    comparison = None
    cont_comparison = None
    if args.backend == "jax" and not args.smoke and not args.no_compare:
        comparison = batched_comparison()
        cont_comparison = continuous_comparison()

    calibration = {
        name: {"exec_time": spec.exec_time, "setup_time": spec.setup_time}
        for name, spec in (getattr(backend, "fn_specs", None) or {}).items()}
    executions = backend.counters().get("n_executions", 0)
    if args.workers > 1:
        # parallel cells executed in worker processes: the shared instance
        # here never ran, so total executions come from the per-cell deltas
        executions = sum(
            row["result"]["backend_counters"].get("n_executions", 0)
            for row in sweep.rows)
    repo_root = Path(__file__).resolve().parent.parent
    default_name = ("BENCH_serving.partial.json" if args.smoke
                    else "BENCH_serving.json")
    out_path = Path(args.out) if args.out else repo_root / default_name
    payload = {
        "schema": 2,
        "bench": "serving",
        "smoke": bool(args.smoke),
        "backend": backend.name,
        "python": sys.version.split()[0],
        "calibration": calibration,
        "executions": executions,
        "wall_s": round(time.time() - t0, 2),
        "data_plane": backend.data_plane(),
        "sweep": sweep.to_dict(),          # full ExperimentResult rows
        "per_class_rows": per_class_rows,  # flattened per-class view
        "batched_comparison": comparison,  # jax-batched vs jax (full runs)
        "continuous_comparison": cont_comparison,  # continuous vs windowed
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {out_path} ({len(sweep)} cells, "
          f"{len(per_class_rows)} per-class rows, "
          f"{payload['executions']} executions)")


if __name__ == "__main__":
    main()
