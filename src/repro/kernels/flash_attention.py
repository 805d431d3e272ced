"""Flash attention (prefill) as a Pallas TPU kernel.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
  * Tiling targets VMEM (~16MB/core), not shared memory: we stream K/V tiles
    HBM->VMEM via BlockSpec index maps while the (block_q, hd) query tile and
    the f32 accumulator stay resident in VMEM scratch across the k-grid.
  * Online softmax state (m, l) lives in SMEM-sized VMEM scratch; matmul
    tiles are chosen as multiples of the 128x128 MXU face (block_q = block_k
    = 128 by default; hd is padded by the caller if not 128-aligned).
  * The k-grid is the innermost sequential dimension, so the accumulator
    carries across k-steps without HBM round-trips (grid iteration on TPU is
    sequential, unlike CUDA thread blocks).
  * GQA is handled by mapping each q-head to its kv-head in the index maps —
    no K/V duplication in HBM.

Causality/window handled by masking within tiles; fully-masked tiles are
skipped via ``pl.when`` on the tile indices (no wasted MXU work).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               block_q: int, block_k: int, sq: int, sk: int,
               causal: bool, window: int, scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # positions: queries are aligned to the END of the kv sequence
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (sk - sq)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # tile-level skip: is any (q,k) pair in this tile live?
    first_q = iq * block_q + (sk - sq)
    last_q = first_q + block_q - 1
    first_k = ik * block_k
    tile_live = True
    if causal:
        tile_live = first_k <= last_q
    if window > 0:
        tile_live = jnp.logical_and(
            tile_live, (first_q - (first_k + block_k - 1)) < window)

    @pl.when(tile_live)
    def _compute():
        # sanitize K/V padding rows: grid padding may contain garbage/NaN and
        # 0 * NaN = NaN would poison the p @ v accumulation
        valid_k = (ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < sk
        q = q_ref[...].astype(f32) * scale              # (bq, hd)
        k = jnp.where(valid_k, k_ref[...].astype(f32), 0.0)   # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        mask &= k_pos < sk
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                             # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                          # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                 # (bq, 1)
        l_scr[...] = l_scr[...] * alpha + p.sum(-1, keepdims=True)
        v = jnp.where(valid_k, v_ref[...].astype(f32), 0.0)   # (bk, hd)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B,Sq,Hq,hd), k/v: (B,Sk,Hkv,hd) -> (B,Sq,Hq,hd).

    Scores are scaled by ``scale`` (default 1/sqrt(hd)).  Queries are
    aligned to the end of the K sequence (decode-suffix convention,
    matching ``ref.flash_attention_ref``)."""
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    nq = pl.cdiv(Sq, bq)
    nk = pl.cdiv(Sk, bk)

    # layout: fold heads into the grid; each program handles one (b*h) pair
    qt = jnp.moveaxis(q, 2, 1).reshape(B * Hq, Sq, hd)
    kt = jnp.moveaxis(k, 2, 1).reshape(B * Hkv, Sk, hd)
    vt = jnp.moveaxis(v, 2, 1).reshape(B * Hkv, Sk, hd)

    kernel = functools.partial(_fa_kernel, block_q=bq, block_k=bk,
                               sq=Sq, sk=Sk, causal=causal, window=window,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((None, bk, hd), lambda h, iq, ik,
                         G=G: (h // G, ik, 0)),
            pl.BlockSpec((None, bk, hd), lambda h, iq, ik,
                         G=G: (h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, hd), q.dtype),
        # online-softmax state persists in VMEM across the sequential k-grid
        scratch_shapes=[
            pltpu.VMEM((bq, 1), f32),
            pltpu.VMEM((bq, 1), f32),
            pltpu.VMEM((bq, hd), f32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out.reshape(B, Hq, Sq, hd), 1, 2)
