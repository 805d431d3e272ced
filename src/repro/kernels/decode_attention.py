"""Flash-decoding: single-token attention over a long KV cache (Pallas TPU).

TPU adaptation notes:
  * Decode attention is memory-bound (arithmetic intensity ~1 FLOP/byte), so
    the kernel's job is to stream the KV cache HBM->VMEM exactly once at full
    bandwidth while the tiny q tile stays resident.
  * All q-heads of one kv-head group are processed together: the (G, hd)
    query tile rides along for every K/V tile, turning a matrix-vector
    stream into a skinny matmul that still feeds the MXU.
  * The cache-length grid axis is innermost/sequential; the online-softmax
    state (m, l, acc) persists in VMEM scratch across it (the "split-K"
    reduction of GPU flash-decoding becomes a sequential VMEM carry on TPU —
    cross-core splitting happens at the shard_map level instead, via the
    sequence-sharded cache + logsumexp combine in the serving layer).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
NEG_INF = -1e30


def _dec_kernel(vlen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                *, block_k: int, L: int):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid_len = vlen_ref[pl.program_id(0)]
    live = (ik * block_k) < valid_len

    @pl.when(live)
    def _compute():
        G = q_ref.shape[0]
        n = jnp.minimum(valid_len, L)
        # the key mask as a column (sanitizes K/V rows) and as score columns;
        # both come from iotas, since Mosaic will not reshape (bk,1)->(1,bk)
        valid = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0) < n
        valid_s = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (G, block_k), 1) < n
        q = q_ref[...].astype(f32)                       # (G, hd)
        k = jnp.where(valid, k_ref[...].astype(f32), 0.0)  # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, bk)
        s = jnp.where(valid_s, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(-1, keepdims=True)
        v = jnp.where(valid, v_ref[...].astype(f32), 0.0)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] /
                      jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     valid_len: jnp.ndarray, *, block_k: int = 256,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B,Hq,hd); k/v: (B,L,Hkv,hd); valid_len: (B,) -> (B,Hq,hd).

    Scores are scaled by 1/sqrt(hd); cache uses prefix layout (slots
    [0, valid_len) hold keys)."""
    B, Hq, hd = q.shape
    L, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)

    bk = min(block_k, L)
    nk = pl.cdiv(L, bk)
    qt = (q * scale).reshape(B, Hkv, G, hd).reshape(B * Hkv, G, hd)
    kt = jnp.moveaxis(k, 2, 1).reshape(B * Hkv, L, hd)
    vt = jnp.moveaxis(v, 2, 1).reshape(B * Hkv, L, hd)
    vlen = jnp.repeat(valid_len.astype(jnp.int32), Hkv)    # (B*Hkv,)

    kernel = functools.partial(_dec_kernel, block_k=bk, L=L)
    # valid lengths ride in SMEM as a scalar-prefetch operand: every index
    # map and the kernel body see the whole (B*Hkv,) array
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * Hkv, nk),
            in_specs=[
                pl.BlockSpec((None, G, hd), lambda h, ik, vl: (h, 0, 0)),
                pl.BlockSpec((None, bk, hd), lambda h, ik, vl: (h, ik, 0)),
                pl.BlockSpec((None, bk, hd), lambda h, ik, vl: (h, ik, 0)),
            ],
            out_specs=pl.BlockSpec((None, G, hd),
                                   lambda h, ik, vl: (h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), f32),
                pltpu.VMEM((G, 1), f32),
                pltpu.VMEM((G, hd), f32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, hd), q.dtype),
        interpret=interpret,
    )(vlen, qt, kt, vt)
    return out.reshape(B, Hkv, G, hd).reshape(B, Hq, hd)
