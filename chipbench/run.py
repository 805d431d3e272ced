"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload phi3-chat --seed 7 --seconds 10 --trace 0

Set-up (weights from the seed, every batch bucket of the cell compiled or
loaded, calibration) counts as ``setup_s``; then ``simulate`` segments run
back to back for ``--seconds`` of wall time; then, with the program's state
freed, the plain float32 reference checks a sample of what the window
served.  ``--trace 1`` profiles the window and reports the per-layer
metrics instead of the end-to-end ones.  The last line of standard output
is one JSON object; the numbers compared for ``correct`` are printed beside
their limits as the last lines of standard error and under ``check``.

The run needs a TPU: on any other platform, or with fewer chips than the
cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse     # noqa: E402
import gc           # noqa: E402
import json         # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402
from pathlib import Path   # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import check as checks      # noqa: E402
import harness              # noqa: E402
import reduce_trace as traces      # noqa: E402
import yardstick            # noqa: E402

TRACE_DIR = ROOT / "build" / "chipbench-trace"


def per_layer(bundle, ctx) -> dict:
    out = {}
    for m in bundle["per_layer"]:
        reader = harness.load_module(HERE / "metrics" / f"{m['name']}.py")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(bundle, seed: int, seconds: float, trace: bool, t_start: float,
             kernels: str = "pallas", fault: str = "",
             controls=()) -> tuple:
    """One run; returns (result line dict, lines for standard error)."""
    import jax
    traffic, cfg = bundle["traffic"], bundle["config"]
    devs = jax.devices()
    dev = devs[0]
    counter = harness.CompileCounter()
    backend, app, arch, mcfg = harness.setup_backend(bundle, seed, kernels)
    rec = harness.Recorder(backend.executor, fault=fault)
    tdir = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tdir = TRACE_DIR
    setup_s = time.perf_counter() - t_start
    segs, w0, w1, stopping = harness.window(bundle, backend, app, seconds,
                                            tdir, rec, counter)
    window_s = w1 - w0
    stats = dev.memory_stats() or {}
    info = [f"window: {len(segs)} segments, {window_s:.3f} s, programs "
            f"compiled or loaded inside it: {counter.n}",
            f"setup_s={setup_s!r} buckets={sorted(backend.executor.bucket_exec_s.items())}"]
    attempted = sum(s.attempted for s in segs)
    completed = sum(s.completed for s in segs)
    e2e = harness.end_to_end(segs, window_s)
    e2e["setup_s"] = setup_s
    totals = {}
    for s in segs:
        for k, v in s.counters.items():
            totals[k] = totals.get(k, 0) + v
    info.append(f"attempted={attempted} completed={completed} "
                f"counters={totals}")
    result = {"correct": False, "attempted": attempted,
              "failed": attempted - completed, "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs),
                         "memory_peak_bytes": stats.get("peak_bytes_in_use")}}
    if trace:
        tr = traces.read_xspace(str(TRACE_DIR), harness.SPAN_PREFIX)
        segspans = [s for s in tr.host_spans if s.name == "segment"]
        lo, hi = segspans[0].start, segspans[-1].end
        busy = traces.busy_ns(tr.device_ops[0], lo, hi) * 1e-9
        result["device"].update(busy_s=busy, window_s=(hi - lo) * 1e-9)
        ctx = dict(segments=segs, window_s=window_s - stopping,
                   batches=rec.batches,
                   counters=totals, completed=completed, config=cfg,
                   traffic=traffic, arch=arch,
                   peaks=yardstick.peaks(dev.device_kind), trace=tr,
                   trace_lo=lo, trace_hi=hi, info=info)
        result["metrics"] = per_layer(bundle, ctx)
        result["breakdown"] = {
            "device_ops": traces.top_ops(tr, lo, hi),
            "idle_gaps": traces.longest_gaps(tr, lo, hi)}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in bundle["end_to_end"]}
    # what the window served, to the host; then free the program's state
    chk = traffic["check"]
    toks, served = checks.sample(rec.batches, traffic["gen_len"],
                                 int(chk["requests"]),
                                 harness.derived_seed(seed, 2))
    missing = checks.missing_rows(rec.batches, traffic["gen_len"])
    del backend, rec
    gc.collect()
    t_ref = time.perf_counter()
    weights = harness.make_weights(bundle, arch, mcfg, seed, program=False)
    readings = checks.compare(arch, cfg, weights, toks, served,
                              traffic["prompt_len"], traffic["gen_len"],
                              int(chk["rows"]), controls)
    del weights
    readings.update(
        unfinished=float(attempted - completed),
        lost=float(sum(s.lost for s in segs)),
        duplicates=float(sum(s.duplicates for s in segs)),
        missing_rows=float(missing))
    info.append(f"reference: {len(toks)} requests, {served.size} served "
                f"tokens, {time.perf_counter() - t_ref:.2f} s")
    limits = {"max_logit_gap": float(chk["max_logit_gap"]),
              "unfinished": 0.0, "lost": 0.0, "duplicates": 0.0,
              "missing_rows": 0.0}
    ok, lines = checks.verdict(readings, limits)
    if counter.n:
        info.append(f"WARNING: {counter.n} programs compiled inside the "
                    f"window")
    result["correct"] = bool(ok)
    result["check"] = {k: {"value": readings.get(k), "limit": v}
                       for k, v in limits.items()}
    for q in controls:
        # the control's first choices in the program's place
        gap = readings[f"control_{q}"]
        ok_q, _ = checks.verdict(dict(readings, max_logit_gap=gap), limits)
        info.append(f"control {q}: max_logit_gap={gap!r} correct={ok_q}")
    return result, info + lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bundle = harness.load_cell(args.workload)
    import jax
    devs = jax.devices()
    need = int(bundle["cell"]["chips"])
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"chipbench: cell {args.workload} needs {need} TPU chip(s); "
              f"JAX found {len(devs)} device(s) of platform "
              f"{devs[0].platform!r} ({devs[0].device_kind})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    result, lines = run_cell(bundle, args.seed, args.seconds,
                             bool(args.trace), T_START)
    sys.stdout.flush()
    for ln in lines:
        print(ln, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
