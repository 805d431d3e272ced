"""Readings for the limit of a cell's check, on the chip at the cell's own
size: for each seed, one short run of the program and, on the same prompts
and served tokens, the float32 reference computed in the lower precisions
of the control (int8 and fp8 at every matmul's inputs), whose first
choices are put in the program's place and judged as a run's would be
(each should come out ``correct=False``).  With ``--fault`` a fault is
planted under the timed path.  The benchmark's runs do not run this.

    python3 chipbench/control.py --workload phi3-chat --seeds 1,2,3 --seconds 5
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness      # noqa: E402
import run          # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fault", default="")
    ap.add_argument("--controls", default="int8,fp8",
                    help="control precisions to read, comma-separated")
    a = ap.parse_args()
    bundle = harness.load_cell(a.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    enable_compile_cache()
    for seed in (int(s) for s in a.seeds.split(",")):
        res, lines = run.run_cell(bundle, seed, a.seconds, False,
                                  time.perf_counter(), fault=a.fault,
                                  controls=tuple(q for q in
                                                 a.controls.split(",") if q))
        ctl = [ln for ln in lines if ln.startswith("control")]
        print("control " + json.dumps(dict(
            workload=a.workload, seed=seed, fault=a.fault,
            correct=res["correct"], check=res["check"],
            controls=ctl, attempted=res["attempted"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
