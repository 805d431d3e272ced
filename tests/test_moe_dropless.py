"""The dropless expert layer and its kernel on the CPU: ``moe_gmm`` in
interpret mode against its oracle, a routing that piles every token on one
expert, the attention kernels' score scale, the published layer order,
and the expert counters the executor hands to the tracing."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.configs import get_config
from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import moe_gmm
from repro.models.layers import moe_dropless
from repro.serving.executor import ModelInstance, ServedModel

KEY = jax.random.PRNGKey(0)


def _experts(G, d, f, key=KEY, stacked=0):
    ks = jax.random.split(key, 3)
    lead = (stacked,) if stacked else ()
    return (jax.random.normal(ks[0], lead + (G, d, f)) / math.sqrt(d),
            jax.random.normal(ks[1], lead + (G, d, f)) / math.sqrt(d),
            jax.random.normal(ks[2], lead + (G, f, d)) / math.sqrt(f))


@pytest.mark.parametrize("sizes", [
    [0, 7, 0, 13, 5, 0],           # empty groups between uneven ones
    [30, 0, 0, 50, 16, 0],         # groups straddling row tiles
    [0, 0, 0, 0, 0, 0],            # no row on any held expert
    [64, 0, 0, 0, 0, 0],           # one expert takes every row
    [1, 1, 1, 1, 1, 1],
])
@pytest.mark.parametrize("extra", [0, 9])   # rows beyond the groups
def test_moe_gmm_matches_its_oracle(sizes, extra):
    d, f = 32, 16
    n = sum(sizes)
    x = jax.random.normal(jax.random.PRNGKey(1), (n + extra, d))
    wg, wu, wd = _experts(len(sizes), d, f)
    gs = jnp.asarray(sizes, jnp.int32)
    got = moe_gmm(x, wg, wu, wd, gs, block_m=16, interpret=True)
    want = ref.moe_gmm_ref(x, wg, wu, wd, gs)
    assert got.shape == x.shape
    np.testing.assert_allclose(got[:n], want[:n], atol=1e-5, rtol=1e-5)
    assert not np.asarray(want[n:]).any()


def test_moe_gmm_reads_one_layer_of_stacked_weights():
    d, f, sizes = 32, 16, [5, 0, 11]
    x = jax.random.normal(jax.random.PRNGKey(2), (16, d))
    wg, wu, wd = _experts(3, d, f, stacked=4)
    gs = jnp.asarray(sizes, jnp.int32)
    for layer in (0, 3):
        got = moe_gmm(x, wg, wu, wd, gs, jnp.int32(layer), block_m=16,
                      interpret=True)
        want = ref.moe_gmm_ref(x, wg[layer], wu[layer], wd[layer], gs)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def _dense(x, p, k, first=0):
    """The routed experts held at ``first..``, every one over every token,
    weighted by its gate: the layer written without dispatch."""
    T, d = x.shape[0] * x.shape[1], x.shape[-1]
    xt = x.reshape(T, d)
    top, idx = jax.lax.top_k(xt @ p["router"], k)
    gates = jax.nn.softmax(top, axis=-1)
    out = jnp.zeros_like(xt)
    for e in range(p["w_gate"].shape[0]):
        g = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1)
        h = jax.nn.silu(xt @ p["w_gate"][e]) * (xt @ p["w_up"][e])
        out = out + g[:, None] * (h @ p["w_down"][e])
    return out.reshape(x.shape)


@pytest.mark.parametrize("kernel", ["xla", "pallas_interpret"])
def test_nothing_is_dropped_when_every_token_picks_one_expert(kernel):
    """A router that sends every token to expert 0: all T*k rows of the
    layer land in one group, and each is computed."""
    d, f, E, k, B, S = 32, 16, 8, 2, 3, 40
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, d))
    wg, wu, wd = _experts(4, d, f)
    router = jnp.zeros((d, E)).at[:, 0].set(jnp.sign(x[0, 0]) * 50.0)
    x = jnp.abs(x) * jnp.sign(x[0, 0])          # every token favours 0
    p = dict(router=router, w_gate=wg, w_up=wu, w_down=wd)
    with jax.default_matmul_precision("highest"):
        y, counts = moe_dropless(x, p, k=k, kernel=kernel)
        want = _dense(x, p, k)
    idx = np.asarray(jax.lax.top_k(x.reshape(-1, d) @ router, k)[1])
    assert (idx[:, 0] == 0).all()
    held = idx < 4
    assert counts.tolist() == [int(held.sum()),
                               len(np.unique(idx[held])), 0]
    np.testing.assert_allclose(y, want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("hd,scale", [(64, None), (64, 1.0 / 64),
                                      (128, 0.0078125)])
def test_attention_kernels_take_a_score_scale(hd, scale):
    B, S, Hq, Hkv = 1, 32, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, hd))
    k = jax.random.normal(ks[1], (B, S, Hkv, hd))
    v = jax.random.normal(ks[2], (B, S, Hkv, hd))
    got = flash_attention(q, k, v, scale=scale, block_q=16, block_k=16,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, scale=scale)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    vlen = jnp.array([20], jnp.int32)
    got = decode_attention(q[:, -1], k, v, vlen, scale=scale,
                           interpret=True)
    want = ref.decode_attention_ref(q[:, -1], k, v, vlen, scale=scale)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    if scale is None:
        # the default is the scale the kernels always had
        np.testing.assert_array_equal(
            ops.attention(q, k, v, backend="xla"),
            ref.flash_attention_ref(q, k, v, scale=None))
        np.testing.assert_allclose(
            want, ref.decode_attention_ref(q[:, -1], k, v, vlen,
                                           scale=1.0 / math.sqrt(hd)),
            atol=1e-6, rtol=1e-6)


def test_granite_layers_run_in_the_published_order():
    cfg = get_config("granite-4.0-h-small")
    assert [i for i, t in enumerate(cfg.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    cut = cfg.with_(n_layers=20, layer_types=cfg.layer_types[:20])
    assert [(g.kind, g.count) for g in cut.groups()] == [
        ("mamba", 5), ("attn", 1), ("mamba", 9), ("attn", 1), ("mamba", 4)]
    assert all(g.moe for g in cut.groups())
    with pytest.raises(ValueError):
        cfg.with_(n_layers=20).groups()


@pytest.mark.parametrize("held", [3, 1])
def test_span_counters_equal_a_count_on_the_host(held):
    """With a zero router every logit ties and each token takes experts
    0..k-1, so the host knows each call's rows and fetches: the executor's
    ``executor.sync`` span must carry those sums."""
    cfg = get_config("granite-4.0-h-small", reduced=True).with_(
        experts_held=held)
    k, L = cfg.experts_per_token, cfg.n_layers
    inst = ModelInstance(ServedModel(cfg, prompt_len=16, gen_len=3,
                                     batch=2))
    inst.setup()
    inst.params = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if getattr(path[-1], "key", "") == "router" else a, inst.params)
    on_held = min(k, held)
    spans.force(True)
    try:
        spans.reset()
        inst.run(seed=5)
        sync = [s for s in spans.timeline() if s.name == "executor.sync"]
    finally:
        spans.force(False)
        spans.reset()
    assert len(sync) == 1
    a = sync[0].attrs
    assert a["moe_prefill_rows"] == 2 * 16 * on_held * L
    assert a["moe_decode_rows"] == 3 * 2 * on_held * L
    assert a["moe_prefill_fetches"] == on_held * L
    assert a["moe_decode_fetches"] == 3 * on_held * L
    assert a["moe_prefill_dropped"] == a["moe_decode_dropped"] == 0


def test_unprofiled_run_reads_no_counters():
    cfg = get_config("granite-4.0-h-small", reduced=True)
    inst = ModelInstance(ServedModel(cfg, prompt_len=16, gen_len=2))
    inst.setup()
    spans.reset()
    inst.run(seed=1)
    assert spans.timeline() == []
