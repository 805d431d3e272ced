"""The plain float32 references against the program's ``kernels="xla"``
path, at a tiny size on the CPU, with the benchmark's weights."""
import numpy as np
import pytest

import chipbench_tiny
import harness
from arch import common

import jax
import jax.numpy as jnp


@pytest.mark.parametrize("kind", ["phi3", "mamba2"])
def test_reference_matches_the_program_in_float32(kind):
    from repro.models import decode_step, forward, init_cache, prefill
    from repro.models.config import ModelConfig
    b = chipbench_tiny.bundle(kind)
    c = b["config"]
    if kind == "mamba2":
        # the program's gated-norm eps, which departs from the published
        # 1e-5: what is compared here is every other equation
        c = dict(c, gated_norm_epsilon=1e-6)
    arch = harness.arch_module(c)
    cfg = ModelConfig(**arch.program_config(c), kernels="xla",
                      param_dtype="float32", compute_dtype="float32")
    specs = jax.eval_shape(
        lambda: __import__("repro.models", fromlist=["init_params"])
        .init_params(cfg, jax.random.PRNGKey(0)))
    pub = common.make_weights(c, arch, specs, 2**33 + 5)
    w = common.make_weights(c, arch, specs, 2**33 + 5, program=True)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                              c["vocab_size"])
    at = jnp.tile(jnp.arange(24), (2, 1))
    ref = np.asarray(arch.logits(c, pub, toks, at))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(forward(cfg, w, toks)[0], np.float32)
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() <= 1e-4 * scale
    # the served path: prefill 20 positions, then decode the rest
    with jax.default_matmul_precision("highest"):
        lg, cache = prefill(cfg, w, toks[:, :20], init_cache(cfg, 2, 24))
        steps = [lg[:, -1]]
        for t in range(20, 23):
            lg, cache = decode_step(cfg, w, cache, toks[:, t:t + 1],
                                    jnp.int32(t))
            steps.append(lg[:, -1])
    served = np.stack([np.asarray(s) for s in steps], 1)
    assert np.abs(served - ref[:, 19:23]).max() <= 1e-4 * scale


def test_weights_come_from_the_seed():
    b = chipbench_tiny.bundle("mamba2")
    c = b["config"]
    arch = harness.arch_module(c)
    specs = {"groups": [{"A_log": jax.ShapeDtypeStruct((2, 8), jnp.float32),
                         "in_proj": jax.ShapeDtypeStruct((2, 64, 8),
                                                         jnp.bfloat16)}],
             "embed": jax.ShapeDtypeStruct((4096, 64), jnp.bfloat16)}
    mk = common.make_weights
    a, a2, other = mk(c, arch, specs, 2**35), mk(c, arch, specs, 2**35), \
        mk(c, arch, specs, 2**35 + 1)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, a2)
    assert all(jax.tree.leaves(same))
    assert not bool((a["embed"] == other["embed"]).all())
    assert a["embed"].dtype == jnp.bfloat16
    assert not bool(a["embed"][c["vocab_size"]:].any())   # padded rows zero
    A = -np.exp(np.asarray(a["groups"][0]["A_log"]))
    assert ((A <= -1.0) & (A >= -16.0)).all()
