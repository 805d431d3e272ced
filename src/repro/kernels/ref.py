"""Pure-jnp oracles for every kernel (the correctness ground truth).

These mirror the contracts of the Pallas kernels exactly; tests sweep shapes
and dtypes asserting allclose between kernel (interpret=True) and oracle.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

f32 = jnp.float32


def _scaled(scores: jnp.ndarray, hd: int,
            scale: Optional[float]) -> jnp.ndarray:
    """Scores times ``scale``, or over sqrt(hd) where it is None."""
    return scores / math.sqrt(hd) if scale is None else scores * scale


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = True, window: int = 0,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """q: (B,Sq,Hq,hd), k/v: (B,Sk,Hkv,hd) -> (B,Sq,Hq,hd).  GQA-aware;
    scores are scaled by ``scale`` (default 1/sqrt(hd))."""
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd).astype(f32)
    scores = _scaled(jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(f32)),
                     hd, scale)
    qp = jnp.arange(Sq) + (Sk - Sq)     # align ends (decode-style offset)
    kp = jnp.arange(Sk)
    m = jnp.ones((Sq, Sk), bool)
    if causal:
        m &= qp[:, None] >= kp[None, :]
    if window > 0:
        m &= (qp[:, None] - kp[None, :]) < window
    scores = jnp.where(m[None, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(f32))
    return out.reshape(B, Sq, Hq, hd).astype(q.dtype)


def decode_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         valid_len: jnp.ndarray,
                         scale: Optional[float] = None) -> jnp.ndarray:
    """One-token attention over a KV cache.

    q: (B,Hq,hd); k/v: (B,L,Hkv,hd); valid_len: (B,) number of valid cache
    slots (prefix layout); scores scaled by ``scale`` (default
    1/sqrt(hd)).  Returns (B,Hq,hd)."""
    B, Hq, hd = q.shape
    L, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, hd).astype(f32)
    scores = _scaled(jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(f32)), hd,
                     scale)
    mask = jnp.arange(L)[None, :] < valid_len[:, None]      # (B,L)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v.astype(f32))
    return out.reshape(B, Hq, hd).astype(q.dtype)


def moe_gmm_ref(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
                w_down: jnp.ndarray, group_sizes: jnp.ndarray,
                layer: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """The SwiGLU expert FFN of each row's group: x (M, d) sorted by group,
    w_gate/w_up ([L,] G, d, f), w_down ([L,] G, f, d) (``layer`` picks one
    of L), group_sizes (G,) -> (M, d), zero at rows beyond the groups.
    Every expert runs over every row and a row keeps its own group's
    result."""
    if w_gate.ndim == 4:
        w_gate, w_up, w_down = w_gate[layer], w_up[layer], w_down[layer]
    hi = jnp.cumsum(group_sizes)
    gid = jnp.searchsorted(hi, jnp.arange(x.shape[0]), side="right")
    out = jnp.zeros(x.shape, x.dtype)
    for g in range(w_gate.shape[0]):
        h = jax.nn.silu(x @ w_gate[g]) * (x @ w_up[g])
        out = jnp.where((gid == g)[:, None], h @ w_down[g], out)
    return out


def ssd_scan_ref(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                 Bm: jnp.ndarray, Cm: jnp.ndarray, chunk: int,
                 init_state: Optional[jnp.ndarray] = None,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD (Mamba2) — delegates to the model-layer reference.
    x: (B,S,H,P), dt: (B,S,H), A: (H,), Bm/Cm: (B,S,N)."""
    # lazy: models.layers imports kernels.ops (dispatch), which imports this
    # module — a top-level import here would close the cycle
    from ..models.layers import ssd_chunked as _ssd_chunked_ref
    return _ssd_chunked_ref(x, dt, A, Bm, Cm, chunk, init_state=init_state)


def ssd_scan_sequential_ref(x, dt, A, Bm, Cm,
                            init_state=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fully sequential (token-by-token) SSM recurrence — the *independent*
    oracle that validates the chunked math itself."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    s0 = (jnp.zeros((B, H, P, N), f32) if init_state is None
          else init_state.astype(f32))

    def step(state, inp):
        xt, dtt, bt, ct = inp
        dA = jnp.exp(dtt.astype(f32) * A[None, :])           # (B,H)
        dBx = jnp.einsum("bn,bhp,bh->bhpn", bt.astype(f32), xt.astype(f32),
                         dtt.astype(f32))
        state = state * dA[..., None, None] + dBx
        y = jnp.einsum("bhpn,bn->bhp", state, ct.astype(f32))
        return state, y

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bm, 1, 0), jnp.moveaxis(Cm, 1, 0))
    final, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), final
