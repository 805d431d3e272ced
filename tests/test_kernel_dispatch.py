"""Kernel dispatch in the serving models (PR 9): ``ModelConfig.kernels``
routes the hot spots (attention, decode attention over KV caches, the SSD
scan) through ``repro.kernels.ops``.  Parity: the Pallas path
(interpret=True on CPU) must agree with the pure-jnp reference on forward,
prefill, and decode — including the ragged continuous-batching decode step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.models import (ModelConfig, decode_step, decode_step_ragged,
                          forward, init_cache, init_params, prefill)

KEY = jax.random.PRNGKey(0)


def _dense(**kw):
    base = dict(name="t-dense", arch_type="dense", n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                compute_dtype="float32", param_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def _ssm(**kw):
    base = dict(name="t-ssm", arch_type="ssm", n_layers=2, d_model=128,
                n_heads=4, n_kv_heads=4, d_ff=256, vocab_size=256,
                ssm_state=16, compute_dtype="float32", param_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


CFGS = [_dense(), _dense(sliding_window=4, name="t-swa"), _ssm()]
# hd=8 against the tests' 12-slot caches: the decode kernel reads them with
# the cache length on lanes (``t-dense``'s hd=16 keeps the head dim there)
LANES = _dense(n_heads=8, name="t-lanes")


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: c.name)
def test_forward_parity_xla_vs_pallas_interpret(cfg):
    p = init_params(cfg, KEY)
    S = 64 if cfg.arch_type == "ssm" else 8      # ssm pads S to the chunk
    toks = jax.random.randint(KEY, (2, S), 0, cfg.vocab_size)
    lx, _ = forward(cfg, p, toks)
    lp, _ = forward(cfg.with_(kernels="pallas_interpret"), p, toks)
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cfg", CFGS + [LANES], ids=lambda c: c.name)
def test_prefill_decode_parity_xla_vs_pallas_interpret(cfg):
    p = init_params(cfg, KEY)
    S = 64 if cfg.arch_type == "ssm" else 8
    toks = jax.random.randint(KEY, (2, S), 0, cfg.vocab_size)
    outs = {}
    for kern in ("xla", "pallas_interpret"):
        c = cfg.with_(kernels=kern)
        lg, cache = prefill(c, p, toks, init_cache(c, 2, S + 4))
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        l1, _ = decode_step(c, p, cache, tok, jnp.int32(S))
        outs[kern] = (lg, l1)
    for a, b in zip(outs["xla"], outs["pallas_interpret"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cfg", CFGS + [LANES], ids=lambda c: c.name)
@pytest.mark.parametrize("kern", ["xla", "pallas_interpret"])
def test_ragged_uniform_t_matches_decode_step(cfg, kern):
    """decode_step_ragged with a uniform position vector IS decode_step."""
    c = cfg.with_(kernels=kern)
    p = init_params(c, KEY)
    S = 64 if c.arch_type == "ssm" else 8
    toks = jax.random.randint(KEY, (2, S), 0, c.vocab_size)
    lg, cache = prefill(c, p, toks, init_cache(c, 2, S + 4))
    tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    l1, c1 = decode_step(c, p, cache, tok, jnp.int32(S))
    l2, c2 = decode_step_ragged(c, p, cache, tok,
                                jnp.full((2,), S, jnp.int32))
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=0)
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=0)


@pytest.mark.parametrize("cfg", [_dense(), _dense(sliding_window=4), LANES],
                         ids=["full", "windowed", "lanes"])
@pytest.mark.parametrize("kern", ["xla", "pallas_interpret"])
def test_ragged_rows_match_independent_sequences(cfg, kern):
    """A ragged batch at different depths must compute, row for row, what
    each row computes alone at its own position (the continuous-batching
    correctness property)."""
    c = cfg.with_(kernels=kern)
    p = init_params(c, KEY)
    max_len = 12
    prompts = [6, 9]                      # row depths differ
    cache = init_cache(c, 2, max_len)
    # fill each row's cache by prefilling it alone and scattering in
    toks = {n: jax.random.randint(jax.random.PRNGKey(n), (1, n), 0,
                                  c.vocab_size) for n in prompts}
    row_caches, row_toks = [], []
    for n in prompts:
        lg1, c1 = prefill(c, p, toks[n], init_cache(c, 1, max_len))
        row_caches.append(c1)
        row_toks.append(jnp.argmax(lg1, axis=-1).astype(jnp.int32))
    cache = jax.tree.map(
        lambda s, r0, r1: s.at[:, 0:1].set(r0.astype(s.dtype))
                           .at[:, 1:2].set(r1.astype(s.dtype)),
        cache, row_caches[0], row_caches[1])
    tok = jnp.concatenate(row_toks)
    t = jnp.asarray(prompts, jnp.int32)
    lr, cr = decode_step_ragged(c, p, cache, tok, t)
    for i, n in enumerate(prompts):
        li, ci = decode_step(c, p, row_caches[i], row_toks[i], jnp.int32(n))
        np.testing.assert_allclose(np.asarray(lr[i:i + 1]), np.asarray(li),
                                   rtol=2e-5, atol=2e-5)
        got = jax.tree.map(lambda s: s[:, i:i + 1], cr)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ci)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-5, atol=2e-5)


def test_kernel_type_normalization_and_validation():
    assert ops.normalize(None) == ops.KernelType.XLA
    assert ops.normalize("pallas") == ops.KernelType.PALLAS
    assert ops.normalize(ops.KernelType.PALLAS_INTERPRET) \
        == ops.KernelType.PALLAS_INTERPRET
    with pytest.raises(ValueError, match="unknown kernel backend"):
        ops.normalize("triton")
    prev = ops.get_backend()
    try:
        ops.set_backend("pallas_interpret")
        assert ops.normalize(None) == ops.KernelType.PALLAS_INTERPRET
    finally:
        ops.set_backend(prev)


def test_kernel_table_covers_every_backend():
    for spot, impls in ops.KERNEL_TABLE.items():
        assert set(impls) == set(ops.KernelType), spot
