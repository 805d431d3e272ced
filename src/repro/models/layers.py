"""Neural building blocks, pure-functional over parameter dicts.

Everything here is plain jnp (the XLA path).  The Pallas kernels in
``repro.kernels`` implement the same contracts for the hot spots and are
selected via ``repro.kernels.ops`` by the model when enabled.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops as kernel_ops

Params = Dict[str, jnp.ndarray]
f32 = jnp.float32


# ---------------------------------------------------------------------------
# Norms / activations / embeddings
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(f32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(f32))).astype(dt)


def swiglu(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
           w_down: jnp.ndarray) -> jnp.ndarray:
    g = jax.nn.silu(x @ w_gate)
    return (g * (x @ w_up)) @ w_down


def gelu_mlp(x: jnp.ndarray, w_up: jnp.ndarray, w_down: jnp.ndarray,
             b_up: Optional[jnp.ndarray] = None,
             b_down: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    h = x @ w_up
    if b_up is not None:
        h = h + b_up
    h = jax.nn.gelu(h)
    y = h @ w_down
    if b_down is not None:
        y = y + b_down
    return y


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(hd: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(f32) * freqs      # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]                    # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(f32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, causal, sliding-window, cross)
# ---------------------------------------------------------------------------


def gqa_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  mask: Optional[jnp.ndarray],
                  scale: Optional[float] = None) -> jnp.ndarray:
    """q: (B,Sq,Hq,hd)  k,v: (B,Sk,Hkv,hd)  mask: broadcastable to
    (B,Hkv,G,Sq,Sk) or (B,1,1,Sq,Sk).  Scores are scaled by ``scale``
    (default 1/sqrt(hd)).  Returns (B,Sq,Hq,hd)."""
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(f32), k.astype(f32))
    scores = scores / math.sqrt(hd) if scale is None else scores * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(f32))
    return out.reshape(B, Sq, Hq, hd).astype(q.dtype)


def causal_window_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                       window: int) -> jnp.ndarray:
    """(Sq,Sk) boolean mask: causal, optionally sliding-window limited.
    ``window`` <= 0 means unlimited lookback."""
    d = q_pos[:, None] - k_pos[None, :]
    m = d >= 0
    if window > 0:
        m &= d < window
    return m


def attention_block(x: jnp.ndarray, p: Params, *, n_heads: int,
                    n_kv_heads: int, hd: int, positions: jnp.ndarray,
                    mask: Optional[jnp.ndarray], rope_theta: float,
                    kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                    kernel: str = "xla", causal: bool = True, window: int = 0,
                    rope: bool = True, scale: Optional[float] = None,
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Self- (or cross-) attention sublayer body (no residual / norm).

    Returns (out, k, v) so callers can stash K/V into a cache.
    ``kv_override`` supplies externally computed K/V (cross-attention or a
    decode-time cache).  ``rope=False`` applies no position encoding;
    ``scale`` is the score scale (default 1/sqrt(hd)).

    ``kernel`` selects the attention implementation (``repro.kernels.ops``):
    the default ``"xla"`` applies the caller-built dense ``mask`` via the
    jnp reference; any Pallas backend instead takes the *structural*
    ``causal``/``window`` description (the flash kernel builds its masks
    per tile — callers pass ``mask=None``)."""
    B, S, d = x.shape
    q = (x @ p["wq"]).reshape(B, S, n_heads, hd)
    if kv_override is None:
        k = (x @ p["wk"]).reshape(B, S, n_kv_heads, hd)
        v = (x @ p["wv"]).reshape(B, S, n_kv_heads, hd)
    else:
        k, v = kv_override
    if rope:
        q = apply_rope(q, positions, rope_theta)
        if kv_override is None:
            k = apply_rope(k, positions, rope_theta)
    if kernel != "xla":
        out = kernel_ops.attention(q, k, v, causal=causal, window=window,
                                   scale=scale, backend=kernel)
    else:
        out = gqa_attention(q, k, v, mask, scale)
    out = out.reshape(B, S, n_heads * hd) @ p["wo"]
    return out, k, v


# ---------------------------------------------------------------------------
# Mixture of Experts (sort-based dispatch with capacity, Switch-style drops)
# ---------------------------------------------------------------------------


def moe_block(x: jnp.ndarray, p: Params, *, n_experts: int, k: int,
              capacity_factor: float, mlp: str = "swiglu",
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B,S,d) -> (y, aux_loss).

    Sort-based dispatch: tokens are routed to their top-k experts, sorted by
    expert id, and scattered into a dense (E, C, d) buffer (tokens beyond an
    expert's capacity are dropped).  Expert FFNs run as batched einsums over
    the leading expert axis — the axis sharded for expert parallelism."""
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    logits = (xt @ p["router"]).astype(f32)                    # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)                      # (T,k)
    gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)

    E = n_experts
    C = max(1, int(math.ceil(k * T / E * capacity_factor)))
    flat_e = eidx.reshape(-1)                                  # (T*k,)
    sort_idx = jnp.argsort(flat_e)                             # stable
    sorted_e = flat_e[sort_idx]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E))      # (E,)
    pos_in_e = jnp.arange(T * k) - seg_start[sorted_e]
    keep = pos_in_e < C
    dest = jnp.where(keep, sorted_e * C + pos_in_e, E * C)     # drop bucket
    token_idx = sort_idx // k

    # Gather-based dispatch: scatter only 4-byte indices (slot -> source
    # token), then move the d-wide rows with a single gather.  A direct
    # row scatter-into-zeros would write the (E*C, d) buffer twice (zero
    # init + scatter) and read it once more; this formulation halves the
    # dispatch HBM traffic (see EXPERIMENTS.md §Perf pair A).
    slot_src = jnp.full((E * C + 1,), T, jnp.int32).at[dest].set(
        token_idx.astype(jnp.int32))
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), x.dtype)], axis=0)
    h = xt_pad[slot_src[:E * C]].reshape(E, C, d)
    if mlp == "swiglu":
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, p["w_gate"]))
        u = jnp.einsum("ecd,edf->ecf", h, p["w_up"])
        out_e = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"])
    else:
        hmid = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", h, p["w_up"]))
        out_e = jnp.einsum("ecf,efd->ecd", hmid, p["w_down"])

    out_flat = jnp.concatenate(
        [out_e.reshape(E * C, d), jnp.zeros((1, d), x.dtype)], axis=0)
    # combine: compose the two permutations (sorted->slot, unsort) into ONE
    # row gather instead of two chained d-wide gathers
    inv = jnp.argsort(sort_idx)
    out_tk = out_flat[dest[inv]].reshape(T, k, d)
    y = (out_tk * gates.astype(x.dtype)[..., None]).sum(axis=1)

    # Switch-style load-balance auxiliary loss
    me = probs.mean(axis=0)                                    # (E,)
    one_hot_top1 = jax.nn.one_hot(eidx[:, 0], E, dtype=f32)
    ce = one_hot_top1.mean(axis=0)
    aux = E * jnp.sum(me * ce)
    return y.reshape(B, S, d), aux


def moe_dropless(x: jnp.ndarray, p: Params, *, k: int, first: int = 0,
                 kernel: str = "xla", layer: Optional[jnp.ndarray] = None,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless routed experts over the experts this chip holds.

    x: (B,S,d) -> (y (B,S,d), counts int32[3]).  The router (``router``,
    (d, E)) scores all E experts in float32; each token takes its top
    ``k`` and weights them by a softmax over those ``k`` logits.  Of the
    T*k assignments, those that fall on held experts (``first`` to
    ``first + G - 1``, the G of ``w_gate``/``w_up``/``w_down``, stacked
    over layers where ``layer`` indexes them) are sorted
    by expert and run through ``kernel_ops.moe_gmm`` (the SwiGLU FFN of
    each expert over its rows); each result is multiplied by its gate and
    summed back into its token.  The row buffer holds every assignment, so
    no token is dropped at any imbalance, and an expert with no rows is
    not computed.  Assignments to experts held elsewhere add nothing here.

    ``counts``: rows that fell on held experts, held experts with at least
    one row (the weight fetches), and held assignments left uncomputed
    (always 0: the check that the dispatch dropped nothing)."""
    B, S, d = x.shape
    T = B * S
    G = p["w_gate"].shape[-3]
    xt = x.reshape(T, d)
    logits = jnp.dot(xt, p["router"], preferred_element_type=f32)   # (T,E)
    top, eidx = jax.lax.top_k(logits, k)                            # (T,k)
    gates = jax.nn.softmax(top, axis=-1)
    held = (eidx >= first) & (eidx < first + G)
    key = jnp.where(held, eidx - first, G).reshape(-1)              # (T*k,)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((G + 1,), jnp.int32).at[key].add(1)[:G]
    rows = kernel_ops.moe_gmm(xt[order // k], p["w_gate"], p["w_up"],
                              p["w_down"], sizes, layer, backend=kernel)
    # back to (token, choice): the row of each assignment, masked where
    # the expert is held elsewhere (those rows were not computed)
    slot = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=order.dtype)).reshape(T, k)
    y = jnp.zeros((T, d), f32)
    for j in range(k):          # one (T, d) gather per choice
        y = y + jnp.where(held[:, j, None],
                          rows[slot[:, j]].astype(f32) * gates[:, j, None],
                          0.0)
    n_held = jnp.sum(held, dtype=jnp.int32)
    counts = jnp.stack([n_held, jnp.sum(sizes > 0, dtype=jnp.int32),
                        n_held - jnp.sum(sizes)])
    return y.astype(x.dtype).reshape(B, S, d), counts


# ---------------------------------------------------------------------------
# Mamba2 (SSD — state-space duality, chunked scan)  [arXiv:2405.21060]
# ---------------------------------------------------------------------------


def _segsum(x: jnp.ndarray) -> jnp.ndarray:
    """Stable segment-sum: out[..., i, j] = sum_{j < t <= i} x[..., t].
    Produces the log-decay matrix L = exp(segsum(dA)) lower-triangular."""
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(xh: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                Bm: jnp.ndarray, Cm: jnp.ndarray, chunk: int,
                init_state: Optional[jnp.ndarray] = None,
                unroll: bool = False,
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mamba2 SSD core over a full sequence (training / prefill).

    xh: (B,S,H,P)  dt: (B,S,H)  A: (H,) negative  Bm,Cm: (B,S,N)
    Returns (y: (B,S,H,P), final_state: (B,H,P,N)).
    """
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    S0 = S
    pad = (-S) % chunk
    if pad:
        # dt=0 on padded steps => decay exp(0)=1 and zero input contribution,
        # so padding never perturbs the state.
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // chunk
    x_c = xh.reshape(Bsz, nc, chunk, H, P)
    dt_c = dt.reshape(Bsz, nc, chunk, H)
    B_c = Bm.reshape(Bsz, nc, chunk, N)
    C_c = Cm.reshape(Bsz, nc, chunk, N)

    dA = dt_c * A[None, None, None, :]                       # (B,nc,Q,H) <= 0
    dA_hbt = jnp.moveaxis(dA, -1, 2)                         # (B,nc,H,Q)
    L = jnp.exp(_segsum(dA_hbt.astype(f32)))                 # (B,nc,H,Q,Q)

    xdt = x_c * dt_c[..., None]                              # input scaled by dt
    # intra-chunk (the "attention-like" quadratic term)
    scores = jnp.einsum("bcqn,bckn->bcqk", C_c.astype(f32), B_c.astype(f32))
    y_diag = jnp.einsum("bchqk,bcqk,bckhp->bcqhp", L, scores,
                        xdt.astype(f32))

    # per-chunk summary state:  sum_k exp(dA_total - cum dA_k) * B_k x_k
    dA_cum = jnp.cumsum(dA_hbt, axis=-1)                     # (B,nc,H,Q)
    decay_out = jnp.exp((dA_cum[..., -1:] - dA_cum).astype(f32))  # (B,nc,H,Q)
    states = jnp.einsum("bchq,bcqn,bcqhp->bchpn", decay_out,
                        B_c.astype(f32), xdt.astype(f32))    # (B,nc,H,P,N)

    # inter-chunk recurrence (sequential over nc)
    chunk_decay = jnp.exp(dA_cum[..., -1].astype(f32))       # (B,nc,H)
    s0 = (jnp.zeros((Bsz, H, P, N), f32) if init_state is None
          else init_state.astype(f32))

    def step(carry, inp):
        dec, st = inp            # (B,H), (B,H,P,N)
        new = carry * dec[..., None, None] + st
        return new, carry        # emit state *entering* the chunk

    decs = jnp.moveaxis(chunk_decay, 1, 0)                   # (nc,B,H)
    sts = jnp.moveaxis(states, 1, 0)                         # (nc,B,H,P,N)
    final_state, prev_states = jax.lax.scan(step, s0, (decs, sts),
                                             unroll=unroll)
    prev_states = jnp.moveaxis(prev_states, 0, 1)            # (B,nc,H,P,N)

    # inter-chunk contribution:  C_q * exp(cum dA_q) * state_in
    decay_in = jnp.exp(dA_cum.astype(f32))                   # (B,nc,H,Q)
    y_off = jnp.einsum("bcqn,bchq,bchpn->bcqhp", C_c.astype(f32),
                       decay_in, prev_states)

    y = (y_diag + y_off).reshape(Bsz, S, H, P)[:, :S0].astype(xh.dtype)
    return y, final_state


def ssd_decode_step(state: jnp.ndarray, x: jnp.ndarray, dt: jnp.ndarray,
                    A: jnp.ndarray, Bm: jnp.ndarray, Cm: jnp.ndarray,
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token recurrent update.
    state: (B,H,P,N)  x: (B,H,P)  dt: (B,H)  Bm,Cm: (B,N)."""
    dA = jnp.exp((dt * A[None, :]).astype(f32))              # (B,H)
    dBx = jnp.einsum("bn,bhp,bh->bhpn", Bm.astype(f32), x.astype(f32),
                     dt.astype(f32))
    new_state = state * dA[..., None, None] + dBx
    y = jnp.einsum("bhpn,bn->bhp", new_state, Cm.astype(f32))
    return y.astype(x.dtype), new_state


def mamba2_block(x: jnp.ndarray, p: Params, *, n_heads: int, head_dim: int,
                 d_state: int, d_conv: int, chunk: int,
                 cache: Optional[Dict] = None, unroll: bool = False,
                 kernel: str = "xla", norm_eps: float = 1e-6,
                 ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Full Mamba2 mixer (in_proj -> conv -> SSD -> gated norm -> out_proj).

    x: (B,S,d).  With ``cache`` (dict with 'conv' (B,d_conv-1,d_xBC) and
    'state' (B,H,P,N)), runs in stateful decode mode (S may be 1).
    ``kernel`` routes the chunked SSD scan through ``repro.kernels.ops``
    (the S=1 recurrent step is jnp on every backend — see KERNEL_TABLE).
    """
    B, S, d = x.shape
    H, P, N = n_heads, head_dim, d_state
    di = H * P
    zxbcdt = x @ p["in_proj"]                                # (B,S,2di+2N+H... )
    z, xBC, dt = jnp.split(zxbcdt, [di, di + di + 2 * N], axis=-1)
    # causal depthwise conv over the sequence
    if cache is not None:
        conv_in = jnp.concatenate([cache["conv"], xBC], axis=1)
        new_conv = conv_in[:, -(d_conv - 1):, :]
    else:
        conv_in = jnp.pad(xBC, ((0, 0), (d_conv - 1, 0), (0, 0)))
        new_conv = conv_in[:, -(d_conv - 1):, :]
    wconv = p["conv_w"]                                      # (d_conv, di+2N)
    xBC = sum(conv_in[:, i:i + S, :] * wconv[i][None, None, :]
              for i in range(d_conv)) + p["conv_b"][None, None, :]
    xBC = jax.nn.silu(xBC).astype(x.dtype)
    xs, Bm, Cm = jnp.split(xBC, [di, di + N], axis=-1)
    xh = xs.reshape(B, S, H, P)
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
    A = -jnp.exp(p["A_log"].astype(f32))                     # (H,)

    if cache is not None and S == 1:
        y1, new_state = kernel_ops.ssd_step(cache["state"], xh[:, 0],
                                            dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                                            backend=kernel)
        y = y1[:, None]
    else:
        init = cache["state"] if cache is not None else None
        if kernel != "xla":
            y, new_state = kernel_ops.ssd(xh, dt.astype(xh.dtype), A, Bm, Cm,
                                          chunk=chunk, init_state=init,
                                          backend=kernel)
        else:
            y, new_state = ssd_chunked(xh, dt.astype(xh.dtype), A, Bm, Cm,
                                       chunk, init_state=init, unroll=unroll)
    y = y + xh * p["D"][None, None, :, None].astype(xh.dtype)
    y = y.reshape(B, S, di)
    # gated RMSNorm (mamba2 style): norm(y * silu(z))
    y = rms_norm(y * jax.nn.silu(z), p["norm_w"], norm_eps)
    out = y @ p["out_proj"]
    new_cache = None
    if cache is not None or True:
        new_cache = {"conv": new_conv, "state": new_state}
    return out, new_cache
