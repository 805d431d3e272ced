"""Compile-only guard for the chip: the Pallas kernels and the served steps
compile for a described TPU v5e at published widths.

Nothing runs, so this says nothing about results or times (the
interpret-mode parity tests in ``test_kernels.py`` cover results); it
catches what interpret mode cannot — block shapes the tiling refuses,
primitives Mosaic does not lower, programs that do not fit the chip.  The
topology is described inside a fixture, never at import: only the worker
that is given this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models import decode_step, init_cache, init_params, prefill

HBM_BYTES = 16e9              # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off (an entry compiled for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in TMPDIR
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# phi3-mini-3.8b: 32 heads of 96, no GQA grouping
PHI3_HEADS = (32, 32, 96)


@pytest.mark.parametrize("seq", [32, 512])
def test_flash_attention_compiles_at_phi3_widths(one_chip, seq):
    H, Hkv, hd = PHI3_HEADS
    q = _sds(one_chip, (1, seq, H, hd))
    kv = _sds(one_chip, (1, seq, Hkv, hd))
    assert _has_kernel(_compile(lambda q, k, v: flash_attention(q, k, v),
                                q, kv, kv))


@pytest.mark.parametrize("batch,cache_len", [(1, 136), (2, 136), (4, 1024)])
def test_decode_attention_compiles_at_phi3_decode_shapes(one_chip, batch,
                                                         cache_len):
    H, Hkv, hd = PHI3_HEADS
    q = _sds(one_chip, (batch, H, hd))
    kv = _sds(one_chip, (batch, cache_len, Hkv, hd))
    vlen = _sds(one_chip, (batch,), jnp.int32)
    assert _has_kernel(_compile(
        lambda q, k, v, n: decode_attention(q, k, v, n), q, kv, kv, vlen))


@pytest.mark.parametrize("batch,seq", [(1, 64), (2, 512)])
def test_ssd_scan_compiles_at_mamba2_widths(one_chip, batch, seq):
    cfg = get_config("mamba2-370m")
    H, P, N = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    args = (_sds(one_chip, (batch, seq, H, P)),
            _sds(one_chip, (batch, seq, H), jnp.float32),
            _sds(one_chip, (H,), jnp.float32),
            _sds(one_chip, (batch, seq, N)), _sds(one_chip, (batch, seq, N)))
    assert _has_kernel(_compile(
        lambda x, dt, A, b, c: ssd_scan(x, dt, A, b, c, chunk=cfg.ssm_chunk),
        *args))


# (arch, step, kernel expected): the mamba2 decode step is the jnp SSM
# recurrence by design (kernels.ops.KERNEL_TABLE["ssd_step"])
SERVED_STEPS = [("phi3-mini-3.8b", "prefill", True),
                ("phi3-mini-3.8b", "decode", True),
                ("mamba2-370m", "prefill", True),
                ("mamba2-370m", "decode", False)]


@pytest.mark.parametrize("arch,step,kernel", SERVED_STEPS)
def test_served_step_compiles_with_pallas_and_fits_one_chip(one_chip, arch,
                                                            step, kernel):
    """The published-width steps the chip smoke test serves (batch 2,
    prompt 128 + 8 generated), from ``jax.eval_shape`` shapes."""
    cfg = get_config(arch).with_(kernels="pallas")
    batch, prompt, gen = 2, 128, 8

    def place(tree):
        return jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = place(jax.eval_shape(lambda k: init_params(cfg, k),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_cache(cfg, batch,
                                                    prompt + gen)))
    if step == "prefill":
        compiled = _compile(lambda p, x, c: prefill(cfg, p, x, c), params,
                            _sds(one_chip, (batch, prompt), jnp.int32), cache)
    else:
        compiled = _compile(lambda p, c, x, t: decode_step(cfg, p, c, x, t),
                            params, cache,
                            _sds(one_chip, (batch, 1), jnp.int32),
                            _sds(one_chip, (), jnp.int32))
    assert _has_kernel(compiled) == kernel
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES
