"""The benchmark's own constants and arithmetic."""
import math

import pytest

import chipbench_tiny  # noqa: F401
import yardstick as ys


def test_peaks_known_and_unknown():
    pk = ys.peaks("TPU v5 lite")
    assert (pk.flops_bf16, pk.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        ys.peaks("cpu")


@pytest.mark.parametrize("fn,args,flops,nbytes", [
    # phi3-mini prefill of 512 positions, one row: 4*512*512*32*96/2
    (ys.attention_cost, (1, 512, 32, 32, 96), 1610612736.0,
     2 * (512 * 32 * 96 * 4)),
    (ys.attention_cost, (2, 8, 4, 2, 16, False), 4.0 * 2 * 8 * 8 * 4 * 16,
     2 * (2 * 8 * 4 * 16 * 2 + 2 * 8 * 2 * 16 * 2)),
    (ys.decode_attention_cost, (8, 513, 32, 32, 96), 4.0 * 8 * 513 * 32 * 96,
     2 * (8 * 513 * 32 * 96 * 2 + 8 * 32 * 96 * 2)),
    (ys.ssd_cost, (8, 256, 32, 64, 128), 6.0 * 8 * 256 * 32 * 64 * 128,
     2 * 8 * 256 * (32 * 64 * 2 + 32 + 256) + 4 * 8 * 32 * 64 * 128 * 2),
])
def test_kernel_work(fn, args, flops, nbytes):
    assert fn(*args) == (flops, nbytes)


def test_bound_takes_the_slower_roof():
    pk = ys.peaks("TPU v5 lite")
    assert ys.bound_seconds(197e12, 1.0, pk) == pytest.approx(1.0)
    assert ys.bound_seconds(1.0, 819e9, pk) == pytest.approx(1.0)


def test_percentile_nearest_rank_with_unfinished():
    xs = list(range(1, 101))
    assert ys.percentile(xs, 95) == 95
    assert ys.percentile(xs[:95] + [math.inf] * 5, 95) == 95
    assert ys.percentile(xs[:94] + [math.inf] * 6, 95) == math.inf
    with pytest.raises(ValueError):
        ys.percentile([], 95)
