"""Serving launcher: `python -m repro.launch.serve --arch <id> ...`

Runs a single-tenant Archipelago serving session through the experiment API
with the ``jax`` execution backend: calibrates the model (real XLA compile =
sandbox setup cost), pre-warms, then drives Poisson traffic through
LBS -> SGS -> workers and reports the full ``ExperimentResult`` (latency
percentiles, deadline adherence, cold starts).  ``--backend stub`` replays
the same pipeline with scripted times (no compiles) for smoke testing.
"""
import argparse

from ..configs import ARCH_IDS, get_config
from ..core import ClusterConfig
from ..serving import ServedModel, ServingApp
from ..sim import Experiment, simulate
from .cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b", choices=ARCH_IDS)
    ap.add_argument("--rps", type=float, default=10.0)
    ap.add_argument("--requests", type=int, default=60,
                    help="expected request count (duration = requests/rps)")
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--gen", type=int, default=4)
    ap.add_argument("--slack", type=float, default=0.5)
    ap.add_argument("--n-sgs", type=int, default=2)
    ap.add_argument("--backend", default="jax",
                    choices=["jax", "jax-batched", "stub", "stub-batched",
                             "modeled"])
    ap.add_argument("--batch-window", type=float, default=0.005,
                    help="batched backends: coalescing window (sim seconds)")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="batched backends: flush when this many invocations "
                         "of one model have gathered")
    ap.add_argument("--kernels", default="xla",
                    choices=["xla", "pallas", "pallas_interpret"],
                    help="jax backends: which implementation serves the "
                         "model hot spots (attention / decode attention / "
                         "SSD scan) — see docs/KERNELS.md")
    ap.add_argument("--batching", default="windowed",
                    choices=["windowed", "continuous"],
                    help="batched backends: request-window coalescing vs "
                         "step-granular continuous batching "
                         "(docs/SERVING.md)")
    ap.add_argument("--stack", default="archipelago")
    ap.add_argument("--warmup", type=float, default=None,
                    help="steady-state window start (exclude the pre-warm "
                         "transient from the reported stats); default: half "
                         "the duration for the jax backend — real compiles "
                         "take seconds and arrivals start at t=0 — else 0")
    args = ap.parse_args()
    enable_compile_cache()
    duration = args.requests / args.rps
    warmup = args.warmup
    real_jax = args.backend in ("jax", "jax-batched")
    if warmup is None:
        warmup = duration / 2.0 if real_jax else 0.0
    backend_kwargs = {}
    if args.backend.endswith("-batched"):
        backend_kwargs = dict(batch_window=args.batch_window,
                              max_batch=args.max_batch,
                              batching=args.batching)
    if real_jax:
        backend_kwargs["kernels"] = args.kernels

    app = ServingApp(
        dag_id=args.arch,
        models={f"{args.arch}/generate": ServedModel(
            get_config(args.arch, reduced=True),
            prompt_len=args.prompt, gen_len=args.gen)},
        slack=args.slack)
    exp = Experiment(
        stack=args.stack,
        backend=args.backend,
        backend_kwargs=backend_kwargs,
        workload_factory="serving_apps",
        workload_kwargs=dict(apps=[app], duration=duration,
                             rps=args.rps, prewarm_per_fn=4),
        cluster=ClusterConfig(n_sgs=args.n_sgs, workers_per_sgs=2,
                              cores_per_worker=2),
        warmup=warmup, drain=10.0)
    if real_jax:
        n_compiles = "one executable per batch bucket" \
            if args.backend == "jax-batched" else "real XLA compile"
        print(f"[serve] calibrating {args.arch} ({n_compiles})...")
    r = simulate(exp)
    backend = r.sim.backend
    for name, spec in (getattr(backend, "fn_specs", None) or {}).items():
        print(f"  {name}: exec={spec.exec_time*1e3:.1f}ms "
              f"setup={spec.setup_time:.1f}s "
              f"SNE={spec.setup_time/spec.exec_time:.0f}x")
    lat = r.latency_percentiles
    print(f"  {r.name}: n={r.n_requests} done={r.n_completed} "
          f"p50={(lat['p50'] or 0)*1e3:.1f}ms "
          f"p99={(lat['p99'] or 0)*1e3:.1f}ms "
          f"deadlines_met={(r.deadline_met_frac or 0)*100:.2f}% "
          f"cold_starts={r.cold_start_count}")
    dp = "".join(f" {k}={v}" for k, v in sorted(r.data_plane.items()))
    print(f"  executions: {backend.counters().get('n_executions', 0)} "
          f"({r.backend} backend{dp})")
    bc = r.backend_counters
    if bc.get("n_batches"):
        print(f"  batches: {bc['n_batches']} "
              f"(mean occupancy "
              f"{bc['n_batched_invocations'] / bc['n_batches']:.2f}, "
              f"max {bc['max_batch_occupancy']}, "
              f"padding efficiency "
              f"{bc['n_batched_invocations'] / bc['n_batch_slots']:.2f})")
    if bc.get("n_decode_ticks"):
        print(f"  continuous: {bc['n_prefill_batches']} prefill batches, "
              f"{bc['n_decode_ticks']} decode ticks "
              f"(mean step occupancy "
              f"{bc['n_step_slots'] / bc['n_decode_ticks']:.2f}, "
              f"max {bc['max_batch_occupancy']})")


if __name__ == "__main__":
    main()
