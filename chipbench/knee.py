"""Sweep steady Poisson rates for a cell's configuration on the chip and
print, per rate, the share of requests that met their deadline, the tail
over the whole segment and over each half of its arrivals (a backlog that
grows shows as a second half far above the first), and what the chip
sustained.  The knee is the highest rate at which at least 99% of requests
meet their deadline with no growing backlog.  Run once when a cell is
defined, with one long segment per rate; the benchmark's runs do not run
it.

    python3 chipbench/knee.py --workload phi3-chat --rates 4,8 --segment-s 60
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness      # noqa: E402


def sweep(bundle, backend, app, rates, segments: int, segment_s: float):
    out = []
    for rate in rates:
        b = copy.deepcopy(bundle)
        b["traffic"]["arrivals"] = {"rps": rate}
        b["traffic"]["segment_s"] = segment_s
        t0 = time.perf_counter()
        segs = [harness.run_segment(b, backend, app, i)
                for i in range(segments)]
        wall = time.perf_counter() - t0
        e = harness.end_to_end(segs, wall)
        att = sum(s.attempted for s in segs)
        nb = sum(s.counters.get("n_batches", 0) for s in segs)
        occ = sum(s.counters.get("n_batched_invocations", 0) for s in segs)
        row = dict(rps=rate, attempted=att,
                   completed=sum(s.completed for s in segs),
                   deadline_met_pct=e["deadline_met_pct"],
                   latency_p95_ms=e["latency_p95_ms"],
                   halves_p95_ms=[[1e3 * x for x in s.halves_p95]
                                  for s in segs],
                   req_per_s=e["req_per_s"], occupancy=occ / max(1, nb),
                   cold_starts=sum(s.cold_starts for s in segs), wall_s=wall)
        print("knee " + json.dumps(row), flush=True)
        out.append(row)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--segments", type=int, default=1)
    ap.add_argument("--segment-s", type=float, default=60.0)
    a = ap.parse_args()
    bundle = harness.load_cell(a.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    enable_compile_cache()
    backend, app, _, _ = harness.setup_backend(bundle, a.seed, "pallas")
    sweep(bundle, backend, app, [float(r) for r in a.rates.split(",")],
          a.segments, a.segment_s)
    return 0


if __name__ == "__main__":
    sys.exit(main())
