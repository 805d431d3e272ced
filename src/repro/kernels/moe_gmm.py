"""Grouped matmul over the experts a chip holds: a SwiGLU expert FFN per
group of rows (Pallas TPU).

Contract (matches ``ref.moe_gmm_ref``):
  x: (M, d) rows sorted by expert, group g's rows at
     [sum(group_sizes[:g]), sum(group_sizes[:g+1]));
  w_gate, w_up: ([L,] G, d, f); w_down: ([L,] G, f, d); group_sizes: (G,)
  int32; layer: which of L stacked layers' experts (a scalar, with
  stacked weights)
  -> (M, d): row r of group g is silu(x_r w_gate[g]) * (x_r w_up[g]) @
     w_down[g].  Rows at or beyond sum(group_sizes) are left unspecified
     (the caller masks them).

TPU adaptation notes (the idea of MegaBlocks' grouped GEMM, laid out for
VMEM):
  * The group sizes are scalar-prefetched.  The wrapper turns them into a
    schedule of grid steps, one per (row tile, group) pair that has rows:
    a tile that straddles a group boundary is visited once per group, and a
    group with no rows gets no step, so its weights are never fetched.  The
    schedule has a static length (row tiles + groups - 1); the steps beyond
    the live ones repeat the last live step's blocks, so the pipeline
    fetches nothing new for them, and compute nothing.
  * One step holds one expert whole in VMEM (gate, up and down: 3 d f
    weights, 18.9 MB at d=4096, f=768) and runs both matmuls on its row
    tile, so the (rows, f) intermediate never touches HBM.  Consecutive
    steps of one expert keep its block, so each held expert with rows is
    fetched once per call: in decode (a few rows per expert) the kernel is
    bound by those weight reads, in prefill (hundreds of rows per expert)
    by the MXU.
  * Rows are not padded to a capacity: the caller's buffer holds every
    assignment (T * k rows), and only the row tiles that hold rows of a
    group are visited.  A step stores only its group's rows of the tile
    (a masked select), so the groups sharing a tile fill it in turn while
    the output block stays resident.
  * The weights may be a model's stacked layers, with the layer's index
    scalar-prefetched: a layer scan that sliced its layer's experts out
    would copy every held expert of the layer each step, where the kernel
    reads only those that have rows.
  * When no row falls on any expert, the pipeline still fetches the first
    step's blocks (the last expert's weights) and computes nothing.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
SUBLANES = 16          # bf16 rows per packed sublane tile


def schedule(group_sizes: jnp.ndarray, tm: int, steps: int):
    """Per grid step the group and the row tile it computes, and the
    number of live steps: the (tile, group) pairs in row order."""
    G = group_sizes.shape[0]
    hi = jnp.cumsum(group_sizes)
    lo = hi - group_sizes
    first = lo // tm
    tiles = jnp.where(group_sizes > 0, (hi - 1) // tm - first + 1, 0)
    cum = jnp.cumsum(tiles)
    live = cum[-1]
    s = jnp.minimum(jnp.arange(steps), jnp.maximum(live - 1, 0))
    g = jnp.minimum(jnp.searchsorted(cum, s, side="right"), G - 1)
    tile = first[g] + s - (cum[g] - tiles[g])
    return (g.astype(jnp.int32), tile.astype(jnp.int32),
            lo.astype(jnp.int32), hi.astype(jnp.int32),
            live.reshape(1).astype(jnp.int32))


def _gmm_kernel(layer_ref, gid_ref, tid_ref, lo_ref, hi_ref, live_ref, x_ref,
                wg_ref, wu_ref, wd_ref, o_ref, *, tm: int):
    s = pl.program_id(0)

    @pl.when(s < live_ref[0])
    def _compute():
        g = gid_ref[s]
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=f32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=f32)
        h = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        y = jnp.dot(h, wd_ref[...], preferred_element_type=f32)
        row = tid_ref[s] * tm + jax.lax.broadcasted_iota(jnp.int32,
                                                         (tm, 1), 0)
        mine = (row >= lo_ref[g]) & (row < hi_ref[g])
        o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def moe_gmm(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
            w_down: jnp.ndarray, group_sizes: jnp.ndarray,
            layer: Optional[jnp.ndarray] = None, *,
            block_m: int = 128, interpret: bool = False) -> jnp.ndarray:
    """x: (M, d); w_gate, w_up: ([L,] G, d, f); w_down: ([L,] G, f, d);
    group_sizes: (G,); layer: the index into L -> (M, d).  See the module
    docstring."""
    if w_gate.ndim == 3:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = 0
    M, d = x.shape
    G, _, f = w_gate.shape[1:]
    if M == 0:
        return x
    tm = min(block_m, -(-M // SUBLANES) * SUBLANES)
    Mp = -(-M // tm) * tm
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    steps = Mp // tm + G - 1
    meta = (jnp.asarray(layer, jnp.int32).reshape(1),
            *schedule(group_sizes.astype(jnp.int32), tm, steps))

    item = x.dtype.itemsize
    expert = 3 * d * f * w_gate.dtype.itemsize
    rows = 2 * tm * d * item
    temps = 4 * tm * (2 * f + d)
    vmem = 2 * (expert + rows) + temps + (4 << 20)

    def rows_map(s, layer, gid, tid, lo, hi, live):
        return tid[s], 0

    def expert_map(s, layer, gid, tid, lo, hi, live):
        return layer[0], gid[s], 0, 0

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(steps,),
            in_specs=[pl.BlockSpec((tm, d), rows_map),
                      pl.BlockSpec((None, None, d, f), expert_map),
                      pl.BlockSpec((None, None, d, f), expert_map),
                      pl.BlockSpec((None, None, f, d), expert_map)],
            out_specs=pl.BlockSpec((tm, d), rows_map),
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
        name="moe_gmm",
    )(*meta, x, w_gate, w_up, w_down)
    return out[:M]
