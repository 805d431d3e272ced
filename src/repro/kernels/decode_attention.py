"""Flash-decoding: single-token attention over a long KV cache (Pallas TPU).

TPU adaptation notes:
  * Decode attention is memory-bound (arithmetic intensity ~1 FLOP/byte), so
    the kernel's job is to stream the KV cache into VMEM exactly once while
    the tiny q tile stays resident, in few grid steps: each step costs a
    fixed overhead larger than a small tile's transfer.
  * K/V are read in the orientation the chip stores the cache in, so XLA
    inserts no relayout copy in front of the kernel.  A (…, L, Hkv, hd)
    cache whose head dim wastes more of the 128-lane tile than its length
    does (phi3's hd=96 at L=544) is stored with the cache length on lanes:
    the kernel then takes (heads, hd, bk) tiles of the cache's (B, Hkv, hd,
    L) view (a bitcast).  Otherwise (hd=128) the head dim is on lanes and it
    takes (bk, Hkv, hd) tiles of the cache as it is (``cache_len_on_lanes``).
  * A grid step covers a block of KV heads of one row over the whole cache
    length or a few large L blocks (``_blocks``); the heads of a block are
    processed one after another, all q-heads of a kv-head group together:
    the (G, hd) query tile rides along for every K/V tile, turning a
    matrix-vector stream into a skinny matmul that still feeds the MXU.
  * The cache-length grid axis is innermost/sequential; the online-softmax
    state (m, l, acc) persists in VMEM scratch across it (the "split-K"
    reduction of GPU flash-decoding becomes a sequential VMEM carry on TPU —
    cross-core splitting happens at the shard_map level instead, via the
    sequence-sharded cache + logsumexp combine in the serving layer).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
NEG_INF = -1e30
LANES = 128
# bytes of one K (or V) block; K and V are double-buffered, so the
# pipeline holds four of them
BLOCK_BYTES = 1 << 20


def _lane_pad(n: int) -> int:
    return -(-n // LANES) * LANES


def cache_len_on_lanes(L: int, hd: int) -> bool:
    """Whether the chip's default layout of a (…, L, Hkv, hd) cache puts
    the cache length on lanes: where padding L to the lane tile wastes a
    smaller share than padding hd does (ties keep hd minor).  It matches
    the layouts the v5e compiler picks for bf16 caches (hd 8-256, L
    12-4096); where it does not, results are the same and XLA inserts a
    relayout copy."""
    return _lane_pad(L) * hd < L * _lane_pad(hd)


def _blocks(L: int, Hkv: int, hd: int, itemsize: int, lanes: bool,
            block_k: Optional[int]) -> Tuple[int, int]:
    """(kv heads, cache slots) per block: the whole cache length where a
    head's slots fit ``BLOCK_BYTES`` (else the most that do, in whole lane
    tiles when L is on lanes), then as many heads as fit beside it (all of
    them when hd is on lanes: (Hkv, hd) is the block's tile)."""
    head = hd * itemsize
    if block_k is None:
        block_k = max(1, BLOCK_BYTES // (head * (1 if lanes else Hkv)))
        if block_k < L and lanes:
            block_k = max(LANES, block_k // LANES * LANES)
    bk = min(block_k, L)
    if not lanes:
        return Hkv, bk
    hb = max(1, min(Hkv, BLOCK_BYTES // (head * bk)))
    while Hkv % hb:
        hb -= 1
    return hb, bk


def _dec_kernel(vlen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                *, block_k: int, L: int, lanes: bool):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid_len = vlen_ref[pl.program_id(0)]
    live = (ik * block_k) < valid_len

    @pl.when(live)
    def _compute():
        hb, G = q_ref.shape[:2]
        n = jnp.minimum(valid_len, L)
        slot = 1 if lanes else 0          # a K/V tile's slot axis
        # the key mask along that axis (sanitizes K/V slots) and as score
        # columns; both come from iotas, since Mosaic will not reshape a
        # column into a row
        valid = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k) if lanes else (block_k, 1), slot) < n
        valid_s = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (G, block_k), 1) < n

        def head(h, carry):
            kt = k_ref[h] if lanes else k_ref[:, h, :]     # (hd,bk)/(bk,hd)
            vt = v_ref[h] if lanes else v_ref[:, h, :]
            q = q_ref[h].astype(f32)                        # (G, hd)
            k = jnp.where(valid, kt.astype(f32), 0.0)
            s = jax.lax.dot_general(q, k, (((1,), (1 - slot,)), ((), ())))
            s = jnp.where(valid_s, s, NEG_INF)              # (G, bk)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * alpha + p.sum(-1, keepdims=True)
            v = jnp.where(valid, vt.astype(f32), 0.0)
            pv = jax.lax.dot_general(p, v, (((1,), (slot,)), ((), ())))
            acc_scr[h] = acc_scr[h] * alpha + pv
            m_scr[h] = m_new
            return carry

        jax.lax.fori_loop(0, hb, head, 0, unroll=True)

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] /
                      jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     valid_len: jnp.ndarray, *,
                     block_k: Optional[int] = None,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B,Hq,hd); k/v: (B,L,Hkv,hd); valid_len: (B,) -> (B,Hq,hd).

    Scores are scaled by 1/sqrt(hd); cache uses prefix layout (slots
    [0, valid_len) hold keys).  ``block_k`` overrides the cache slots per
    block, which are otherwise sized from the shapes (``_blocks``)."""
    B, Hq, hd = q.shape
    L, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    lanes = cache_len_on_lanes(L, hd)
    hb, bk = _blocks(L, Hkv, hd, k.dtype.itemsize, lanes, block_k)
    nk = pl.cdiv(L, bk)

    qt = (q * scale).reshape(B, Hkv, G, hd)
    if lanes:
        # (B, Hkv, hd, L): the stored order, so this transpose is a bitcast
        k, v = (jnp.transpose(a, (0, 2, 3, 1)) for a in (k, v))
        kv_spec = pl.BlockSpec((None, hb, hd, bk),
                               lambda b, h, ik, vl: (b, h, 0, ik))
    else:
        kv_spec = pl.BlockSpec((None, bk, hb, hd),
                               lambda b, h, ik, vl: (b, ik, h, 0))
    head_spec = pl.BlockSpec((None, hb, G, hd),
                             lambda b, h, ik, vl: (b, h, 0, 0))

    kernel = functools.partial(_dec_kernel, block_k=bk, L=L, lanes=lanes)
    # valid lengths ride in SMEM as a scalar-prefetch operand: every index
    # map and the kernel body see the whole (B,) array
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv // hb, nk),
            in_specs=[head_spec, kv_spec, kv_spec],
            out_specs=head_spec,
            scratch_shapes=[
                pltpu.VMEM((hb, G, 1), f32),
                pltpu.VMEM((hb, G, 1), f32),
                pltpu.VMEM((hb, G, hd), f32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        interpret=interpret,
    )(valid_len.astype(jnp.int32), qt, k, v)
    return out.reshape(B, Hq, hd)
