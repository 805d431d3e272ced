"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

TPU adaptation notes (vs the Triton/CUDA SSD kernels of the Mamba2 release):
  * One grid program per (batch x head); the chunk axis is the innermost
    *sequential* grid dimension, so the (P, N) inter-chunk state lives in
    VMEM scratch and never round-trips HBM — the GPU implementation's
    separate "state-passing" kernel disappears into the sequential grid.
  * The intra-chunk quadratic term is a (Q,Q) matmul pair, MXU-friendly for
    Q = 64..128; the decay matrix is built in-register from the in-chunk
    prefix sums of dA (computed by the wrapper, O(S) work, and streamed in
    as a column and a row) rather than precomputed as (Q, Q) in HBM.
  * All decay math is f32; inputs stream in bf16.

Contract matches ``ref.ssd_scan_ref`` / the sequential oracle:
  x: (B,S,H,P)  dt: (B,S,H) (post-softplus)  A: (H,) negative
  Bm, Cm: (B,S,N)  ->  y: (B,S,H,P), final_state: (B,H,P,N)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32


def _ssd_kernel(x_ref, dt_ref, cum_ref, cumr_ref, b_ref, c_ref, s0_ref,
                y_ref, st_ref, state_scr, *, Q: int):
    ic = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ic == 0)
    def _init():
        # seed the inter-chunk carry from the caller's state (decode-time
        # prefill over an existing cache); zeros for a fresh sequence
        state_scr[...] = s0_ref[0].astype(f32)

    x = x_ref[0].astype(f32)           # (Q, P)
    dt = dt_ref[0].astype(f32)         # (Q, 1)
    cum = cum_ref[0]                   # (Q, 1)  in-chunk cumsum of dt*A[h]
    cum_row = cumr_ref[0, 0]           # (1, Q)  the same, laid out as a row
    Bm = b_ref[0].astype(f32)          # (Q, N)
    Cm = c_ref[0].astype(f32)          # (Q, N)

    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # the chunk's total decay: the last lane of the row (an exact masked sum)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    total = jnp.sum(jnp.where(last, cum_row, 0.0), axis=1,
                    keepdims=True)     # (1, 1)
    xdt = x * dt                       # (Q, P)

    # intra-chunk: y_diag = (L .* (C B^T)) @ xdt, L = exp(segsum(dA))
    seg = cum - cum_row                # (Q, Q): cum_i - cum_j
    L = jnp.where(qi >= kj, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (Q,Q)
    y = jax.lax.dot(L * scores, xdt)   # (Q, P)

    # inter-chunk: y_off = (C .* exp(cum)) @ state_prev^T
    state_prev = state_scr[...]        # (P, N)
    decay_in = jnp.exp(cum)            # (Q, 1)
    y += jax.lax.dot_general(Cm * decay_in, state_prev,
                             (((1,), (1,)), ((), ())))  # (Q, P)
    y_ref[0, :, :] = y.astype(y_ref.dtype)

    # state update: state = state * exp(total) + xdt^T @ (B .* decay_out)
    decay_out = jnp.exp(total - cum)                    # (Q, 1)
    contrib = jax.lax.dot_general(xdt, Bm * decay_out,
                                  (((0,), (0,)), ((), ())))  # (P, N)
    state_scr[...] = state_prev * jnp.exp(total) + contrib

    @pl.when(ic == nc - 1)
    def _emit_state():
        st_ref[0, :, :] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             Bm: jnp.ndarray, Cm: jnp.ndarray, *, chunk: int = 64,
             init_state: jnp.ndarray = None, interpret: bool = False):
    """See module docstring.  S must be a multiple of ``chunk`` (the ops.py
    wrapper pads with dt=0, which provably leaves the state untouched).
    ``init_state`` (B,H,P,N) seeds the recurrence (None = zeros)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, "pad S to a chunk multiple (see ops.ssd)"
    nc = S // chunk

    xt = jnp.moveaxis(x, 2, 1).reshape(B * H, S, P)
    dtt = jnp.moveaxis(dt, 2, 1).reshape(B * H, S, 1)
    dAt = dtt * A.reshape(1, H, 1, 1).repeat(B, 0).reshape(B * H, 1, 1)
    # in-chunk prefix sums of dA, taken here because Mosaic lowers neither
    # cumsum nor a (Q, 1) -> (1, Q) transpose; the kernel gets a column and
    # a row copy (the same jnp.cumsum the reference takes)
    cum = jnp.cumsum(dAt.astype(f32).reshape(B * H, nc, chunk), axis=-1)
    cum_col = cum.reshape(B * H, S, 1)
    cum_row = cum.reshape(B * H, nc, 1, chunk)
    bt = Bm                                             # (B, S, N)
    ct = Cm
    s0 = (jnp.zeros((B * H, P, N), f32) if init_state is None
          else init_state.astype(f32).reshape(B * H, P, N))

    kernel = functools.partial(_ssd_kernel, Q=chunk)
    y, st = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda h, c: (h, c, 0, 0)),
            pl.BlockSpec((1, chunk, N), lambda h, c, H=H: (h // H, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda h, c, H=H: (h // H, c, 0)),
            pl.BlockSpec((1, P, N), lambda h, c: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, P, N), lambda h, c: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B * H, P, N), f32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), f32)],
        interpret=interpret,
    )(xt, dtt, cum_col, cum_row, bt, ct, s0)
    y = jnp.moveaxis(y.reshape(B, H, S, P), 1, 2)
    return y, st.reshape(B, H, P, N)
