"""Unit tests for the roofline toolchain's HLO parsing (no 512-device mesh
needed — those paths are covered by the launch sweeps themselves)."""
import pytest

from repro.configs import get_config
from repro.launch.roofline import (_shape_bytes, model_flops,
                                   parse_collective_bytes)


def test_shape_bytes_basic():
    assert _shape_bytes("f32[4,8]{1,0}") == 4 * 8 * 4
    assert _shape_bytes("bf16[2,3,5]") == 2 * 3 * 5 * 2
    assert _shape_bytes("pred[7]") == 7
    assert _shape_bytes("(f32[4], bf16[8,2])") == 16 + 32
    assert _shape_bytes("s32[]") == 0 or _shape_bytes("s32[]") == 4  # scalar


def test_parse_collectives_ring_factors():
    hlo = """
HloModule test
ENTRY main {
  %p0 = bf16[16,128]{1,0} parameter(0)
  %ag = bf16[256,128]{1,0} all-gather(%p0), replica_groups=[1,16]<=[16], dimensions={0}
  %ar = bf16[256,128]{1,0} all-reduce(%ag), replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = bf16[16,128]{1,0} collective-permute(%p0), source_target_pairs={{0,1}}
  ROOT %t = (bf16[256,128]{1,0}) tuple(%ar)
}
"""
    out = parse_collective_bytes(hlo, default_group=16)
    ag_result = 256 * 128 * 2
    assert out["all-gather"] == pytest.approx((15 / 16) * ag_result)
    ar_operand = 256 * 128 * 2
    assert out["all-reduce"] == pytest.approx(2 * (3 / 4) * ar_operand)
    assert out["collective-permute"] == pytest.approx(16 * 128 * 2)
    assert out["all-to-all"] == 0.0


def test_parse_collectives_ignores_non_collectives():
    hlo = "%x = f32[8]{0} add(%a, %b)\n%y = f32[8]{0} dot(%x, %x)\n"
    out = parse_collective_bytes(hlo, default_group=4)
    assert sum(out.values()) == 0.0


def test_model_flops_moe_counts_active_only():
    dense = get_config("minitron-8b")
    moe = get_config("mixtral-8x22b")
    # mixtral total params >> active params; model_flops must use active
    assert moe.param_count() > 2.5 * moe.active_param_count()
    f_train = model_flops(moe, "train_4k")
    assert f_train == pytest.approx(
        6.0 * moe.active_param_count() * 256 * 4096)
    # decode: one token per sequence
    assert model_flops(dense, "decode_32k") == pytest.approx(
        2.0 * dense.param_count() * 128)


def test_param_count_magnitudes():
    """Sanity: analytic parameter counts are in each card's ballpark."""
    expect = {"minicpm-2b": (2.0e9, 3.3e9),
              "phi3-mini-3.8b": (3.3e9, 4.4e9),
              "minitron-8b": (7.0e9, 10.0e9),
              "mamba2-370m": (0.3e9, 0.5e9),
              "mixtral-8x22b": (120e9, 150e9),
              "gemma3-1b": (0.8e9, 1.6e9)}
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B outside [{lo},{hi}]"


def test_peak_table_is_keyed_by_device_kind_and_names_its_source():
    from repro.launch.mesh import V5E, peak_rates
    v5e = peak_rates(V5E)
    assert (v5e.flops_bf16, v5e.hbm_bw) == (197e12, 819e9)
    assert '"TPU v5e"' in v5e.source


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_peak_table_raises_for_an_unknown_device_kind(kind):
    from repro.launch.mesh import peak_rates
    with pytest.raises(ValueError, match="no published peaks"):
        peak_rates(kind)
