"""The check of a mamba2 cell at a tiny size on the CPU: a sound run is
correct, the control (the float32 reference computed with int8 or fp8
matmul inputs, put in the program's place) reads above the limit, and a
run with its timed path broken underneath comes out not correct.  The
chip runs of the same at the cell's own size are ``chipbench/control.py``."""
import time

import pytest

import chipbench_tiny
import run

LIMIT = 0.01     # the tiny size's own limit, between the readings below
SEED = 2**40 + 7


def _run(fault="", controls=()):
    return run.run_cell(chipbench_tiny.bundle("mamba2", limit=LIMIT), SEED,
                        0.3, False, time.perf_counter(), kernels="xla",
                        fault=fault, controls=controls)


def test_sound_run_passes_and_the_control_fails():
    res, lines = _run(controls=("fp8",))
    assert res["correct"], lines
    assert res["check"]["max_logit_gap"]["value"] <= LIMIT
    line = next(ln for ln in lines if ln.startswith("control fp8"))
    gap, verdict = line.split()[-2:]
    assert float(gap.split("=")[1]) > LIMIT
    assert verdict == "correct=False"
    assert lines[-1].startswith("check ")


@pytest.mark.parametrize("fault,reading", [
    ("token", "max_logit_gap"), ("stale_cache", "max_logit_gap"),
    ("drop", "missing_rows")])
def test_broken_timed_path_is_not_correct(fault, reading):
    res, lines = _run(fault=fault)
    assert not res["correct"]
    r = res["check"][reading]
    assert r["value"] > r["limit"], lines
