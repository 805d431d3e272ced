"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.decode_attention import (cache_len_on_lanes,
                                            decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import (decode_attention_ref, flash_attention_ref,
                               ssd_scan_ref, ssd_scan_sequential_ref)
from repro.kernels.ssd_scan import ssd_scan

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=3e-5, atol=3e-5)


FA_CASES = [
    # B, Sq, Sk, Hq, Hkv, hd, causal, window
    (2, 128, 128, 4, 2, 64, True, 0),
    (1, 256, 256, 4, 1, 128, True, 0),
    (2, 64, 192, 4, 4, 64, True, 0),       # q aligned to kv suffix
    (1, 256, 256, 8, 2, 64, True, 64),     # sliding window
    (1, 96, 96, 2, 2, 32, False, 0),       # ragged, bidirectional
    (2, 100, 228, 6, 3, 64, True, 100),    # ragged + window + GQA
    # edge shapes (PR 9): decode-suffix q, window wider than the cache,
    # single-token windowed decode, sequences smaller than one block
    (2, 1, 128, 4, 2, 64, True, 0),        # Sq=1: flash as decode suffix
    (1, 64, 64, 4, 2, 64, True, 128),      # window > Sk: full-causal limit
    (2, 1, 96, 6, 3, 64, True, 32),        # Sq=1 + window + GQA + ragged Sk
    (1, 17, 17, 2, 1, 32, True, 8),        # S < block, ragged + window
]


@pytest.mark.parametrize("case", FA_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_oracle(case, dtype):
    B, Sq, Sk, Hq, Hkv, hd, causal, window = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, hd), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True, block_q=64, block_k=64)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


DEC_CASES = [
    (2, 512, 8, 2, 64, 128),
    (1, 1000, 4, 4, 128, 256),
    (3, 256, 4, 1, 32, 64),
    (2, 300, 6, 3, 64, 128),
    # edge shapes (PR 9): cache smaller than one block, MHA limit
    (2, 33, 4, 2, 64, 128),                # L < block_k, ragged
    (1, 64, 1, 1, 32, 64),                 # single-head MHA
    # phi3's serving shape (cache length on lanes), blocks derived
    (1, 544, 32, 32, 96, None),
    (2, 544, 32, 32, 96, None),
    # hd=128 GQA (head dim on lanes), L not a multiple of the block
    (2, 300, 8, 2, 128, 128),
]


@pytest.mark.parametrize("fill,hd", [("one", 64), ("full", 64),
                                     ("one", 128), ("full", 128)],
                         ids=["one", "full", "one-hd128", "full-hd128"])
def test_decode_attention_valid_len_extremes(fill, hd):
    """valid_len at both ends of the legal range: 1 (only the first cache
    slot attends) and L (the whole cache attends), in both orientations:
    the cache length on lanes (hd=64) and the head dim on lanes (hd=128)."""
    B, L, Hq, Hkv = 2, 128, 4, 2
    assert cache_len_on_lanes(L, hd) == (hd == 64)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, hd))
    k = jax.random.normal(ks[1], (B, L, Hkv, hd))
    v = jax.random.normal(ks[2], (B, L, Hkv, hd))
    vlen = jnp.full((B,), 1 if fill == "one" else L, jnp.int32)
    out = decode_attention(q, k, v, vlen, block_k=64, interpret=True)
    ref = decode_attention_ref(q, k, v, vlen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("case", DEC_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_vs_oracle(case, dtype):
    B, L, Hq, Hkv, hd, bk = case
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Hq, hd), dtype)
    k = jax.random.normal(ks[1], (B, L, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, L, Hkv, hd), dtype)
    vlen = jax.random.randint(ks[3], (B,), 1, L + 1)
    out = decode_attention(q, k, v, vlen, block_k=bk, interpret=True)
    ref = decode_attention_ref(q, k, v, vlen)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


SSD_CASES = [
    (2, 128, 4, 64, 32, 64),
    (1, 64, 2, 32, 16, 16),
    (2, 256, 3, 64, 64, 64),
    (1, 192, 2, 32, 128, 64),
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_vs_oracles(case, dtype):
    B, S, H, P, N, chunk = case
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, N), dtype)
    y, st = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, sr = ssd_scan_ref(x, dt, A, Bm, Cm, chunk)
    # bf16 inputs: long accumulation chains differ in summation order
    tol = dict(rtol=6e-2, atol=6e-2) if dtype == jnp.bfloat16 else _tol(dtype)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)
    if dtype == jnp.float32:
        # the chunked math itself vs an independent sequential recurrence
        ys, ss = ssd_scan_sequential_ref(x, dt, A, Bm, Cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ys),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(st), np.asarray(ss),
                                   rtol=1e-4, atol=1e-4)


def test_ssd_scan_init_state_parity():
    """The kernel's seeded inter-chunk carry (decode-time prefill over an
    existing cache) must match both oracles given the same init_state."""
    B, S, H, P, N, chunk = 2, 128, 2, 32, 16, 64
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    s0 = jax.random.normal(ks[5], (B, H, P, N))
    y, st = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, init_state=s0,
                     interpret=True)
    yr, sr = ssd_scan_ref(x, dt, A, Bm, Cm, chunk, init_state=s0)
    ys, ss = ssd_scan_sequential_ref(x, dt, A, Bm, Cm, init_state=s0)
    for got, ref in ((y, yr), (y, ys), (st, sr), (st, ss)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_ops_dispatch_backends():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 64))
    k = jax.random.normal(ks[1], (1, 64, 2, 64))
    v = jax.random.normal(ks[2], (1, 64, 2, 64))
    a = ops.attention(q, k, v, backend="xla")
    b = ops.attention(q, k, v, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=3e-5, atol=3e-5)


def test_ops_ssd_pads_ragged_seq():
    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 1, 100, 2, 32, 16     # S not a chunk multiple
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y1, s1 = ops.ssd(x, dt, A, Bm, Cm, chunk=64, backend="pallas_interpret")
    y2, s2 = ops.ssd(x, dt, A, Bm, Cm, chunk=64, backend="xla")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)
