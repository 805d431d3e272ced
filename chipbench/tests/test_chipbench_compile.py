"""Compile-only: the roofline rule on the module the chip's compiler makes
of phi3-chat's decode step at bucket 1, for a described TPU v5e.

Nothing runs.  The test reads, from the compiled text, where the decode
attention kernel's K and V live and which ops stage them, as the
``decode_attention_roofline`` reader does from the profile's own HLO.  The
topology is described inside a fixture, never at import: only the worker
that is given this file loads the TPU compiler.
"""
from collections import Counter

import pytest

import chipbench_tiny  # noqa: F401  (puts the benchmark on sys.path)
from chipbench_tiny import BENCH
import harness
import hlo
import reduce_trace as traces


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off (an entry compiled for a described chip cannot be read back)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

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


def _decode_module(sharding, bucket: int) -> hlo.Module:
    """The cell's decode step for ``bucket`` rows, as the executor builds
    it (``init_cache(cfg, bucket, prompt + gen)``), compiled."""
    import jax
    import jax.numpy as jnp
    from repro.models import decode_step, init_cache, init_params

    bundle = harness.load_cell("phi3-chat")
    _, mcfg, served, _ = harness.build_program(bundle, "pallas")

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    params = place(jax.eval_shape(lambda: init_params(
        mcfg, jax.random.PRNGKey(0))))
    cache = place(jax.eval_shape(lambda: init_cache(
        mcfg, bucket, served.prompt_len + served.gen_len)))
    compiled = jax.jit(lambda p, c, x, t: decode_step(mcfg, p, c, x, t)).lower(
        params, cache,
        jax.ShapeDtypeStruct((bucket, 1), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)).compile()
    return hlo.Module(compiled.as_text())


def test_decode_attention_kv_staging_at_bucket_1(one_chip):
    """K and V come from the stacked cache in HBM, so their bytes count.
    Where the kernel gets them in VMEM, each is staged by one fusion that
    slices the layer out of the cache (the op that reads HBM, last in the
    chain) and at most one relayouting copy, and every op charged only
    moves data: at the parent, constant_dynamic-slice_fusion -> copy ->
    dynamic_update_slice -> bitcast."""
    mod = _decode_module(one_chip, 1)
    kernels = [n for n in mod.where if traces.kind(n) == "decode_attention"
               and mod.instr(n).opcode == "custom-call"]
    assert len(kernels) == 1
    reader = harness.load_module(BENCH / "metrics"
                                 / "decode_attention_roofline.py")
    ops = {o.role: o for o in hlo.staging(mod, kernels[0], *reader.OPERANDS)}
    call = mod.instr(kernels[0])
    for role, value in (("k", call.operands[2]), ("v", call.operands[3])):
        o = ops[role]
        assert o.hbm, o
        staged = hlo.in_vmem(mod.instr(value).shape)
        assert bool(o.charged) == staged
        if not staged:
            continue
        assert o.end == o.charged[-1]
        assert traces.kind(o.end).endswith("dynamic-slice_fusion"), o
        kinds = Counter(traces.kind(n) for n in o.charged)
        assert kinds[traces.kind(o.end)] == 1 and kinds["copy"] <= 1
        for name in o.charged:
            ins = mod.instr(name)
            assert ins.opcode != "bitcast"
            assert hlo.moved_from(mod, ins) is not None, name
