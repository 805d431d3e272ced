"""Helpers the architectures' benchmark files share: weights drawn from the
seed leaf by leaf in one jitted call (published, or in the program's
layout), per-layer upcasts, and the matmul of the reference in float32 or
in the control's lower precision."""
from __future__ import annotations

import zlib
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

f32 = jnp.float32


def leaf_name(path) -> str:
    return str(getattr(path[-1], "key", getattr(path[-1], "idx", path[-1])))


def make_weights(c: Dict[str, Any], arch, specs, seed: int,
                 program: bool = False):
    """The weight tree with the structure, shapes and dtypes of ``specs``
    (``jax.ShapeDtypeStruct`` leaves), each leaf's published value drawn by
    ``arch.weight`` from a key folded out of the seed and the leaf's path,
    and with ``program`` mapped into the program's layout by
    ``arch.to_program``.  One jitted call on the default device; the same
    seed gives the same weights."""
    paths = jax.tree_util.tree_flatten_with_path(specs)[0]
    treedef = jax.tree_util.tree_structure(specs)

    def build(key):
        leaves = []
        for path, s in paths:
            k = jax.random.fold_in(
                key, zlib.crc32(jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF)
            name = leaf_name(path)
            w = arch.weight(c, name, k, s.shape)
            if program:
                w = arch.to_program(c, name, w)
            if w.shape != s.shape:
                raise ValueError(f"weight {jax.tree_util.keystr(path)}: "
                                 f"{w.shape}, want {s.shape}")
            leaves.append(w.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from any whole number (the seed may exceed 32 bits)."""
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def zero_rows(w, keep: int, axis: int):
    """Zero the entries of ``w`` at index >= ``keep`` along ``axis`` (the
    padded rows of a vocabulary)."""
    idx = jnp.arange(w.shape[axis])
    shape = [1] * w.ndim
    shape[axis] = w.shape[axis]
    return jnp.where((idx < keep).reshape(shape), w, 0.0)


@jax.jit
def _slice_f32(g, i):
    return jax.tree.map(lambda a: a[i].astype(f32), g)


def layer_f32(group, i: int):
    """Layer ``i`` of a stacked group of weights, upcast to float32."""
    return _slice_f32(group, jnp.int32(i))


def _quantize(x, axis: int, dtype):
    """Round ``x`` to ``dtype`` with one scale per slice along ``axis``
    (per row of activations, per output column of weights)."""
    top = {jnp.int8: 127.0, jnp.float8_e4m3fn: 448.0}[dtype]
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    q = x / s
    q = jnp.round(q) if dtype == jnp.int8 else q
    return q.astype(dtype).astype(f32) * s


QUANT = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


def matmul(quant: str) -> Callable:
    """``x @ w`` in float32, or with both inputs first rounded to the
    control's precision (``"int8"`` or ``"fp8"``)."""
    if not quant:
        return lambda x, w: x @ w
    dt = QUANT[quant]
    return lambda x, w: _quantize(x, -1, dt) @ _quantize(w, 0, dt)
