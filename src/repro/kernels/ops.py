"""Public kernel entry points with backend dispatch.

``backend`` (a :class:`KernelType` or its string value):
  "xla"              pure-jnp reference path (default on CPU; what the
                     dry-run lowers)
  "pallas"           compiled Pallas TPU kernels (TPU targets)
  "pallas_interpret" Pallas kernels executed in interpret mode (CPU
                     validation; used by the kernel test suite and CI)

The model stack (``repro.models``) threads ``ModelConfig.kernels`` into
these entry points, so the choice is a sweepable ``Experiment``
``backend_kwargs`` axis (``kernels="pallas"`` on the jax backends) — see
``docs/KERNELS.md`` for the full dispatch table and the recipe for
registering a new kernel.
"""
from __future__ import annotations

from enum import Enum
from typing import Optional, Tuple, Union

import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention as _dec_pallas
from .flash_attention import flash_attention as _fa_pallas
from .moe_gmm import moe_gmm as _gmm_pallas
from .ssd_scan import ssd_scan as _ssd_pallas


class KernelType(Enum):
    """Which implementation services a hot-spot call (mamba-jax idiom)."""

    XLA = "xla"
    PALLAS = "pallas"
    PALLAS_INTERPRET = "pallas_interpret"


def normalize(backend: Union[str, KernelType, None]) -> KernelType:
    """Coerce a user-facing backend choice (string, enum, or None =
    process default) to a :class:`KernelType`, validating the name."""
    if backend is None:
        return KernelType(_BACKEND)
    if isinstance(backend, KernelType):
        return backend
    try:
        return KernelType(backend)
    except ValueError:
        raise ValueError(
            f"unknown kernel backend {backend!r}; choose from "
            f"{[k.value for k in KernelType]}") from None


_BACKEND = "xla"


def set_backend(backend: Union[str, KernelType]) -> None:
    global _BACKEND
    _BACKEND = normalize(backend).value


def get_backend() -> str:
    return _BACKEND


# Dispatch table: hot spot -> {KernelType: implementation}.  The decode-side
# SSM recurrence (``ssd_step``) deliberately maps every backend to the jnp
# reference: at S=1 the update is a handful of memory-bound element-wise ops
# with nothing for a Pallas kernel to fuse beyond what XLA already does.
KERNEL_TABLE = {
    "attention": {
        KernelType.XLA: "ref.flash_attention_ref",
        KernelType.PALLAS: "flash_attention (compiled)",
        KernelType.PALLAS_INTERPRET: "flash_attention (interpret)",
    },
    "decode_attention": {
        KernelType.XLA: "ref.decode_attention_ref",
        KernelType.PALLAS: "decode_attention (compiled)",
        KernelType.PALLAS_INTERPRET: "decode_attention (interpret)",
    },
    "moe_gmm": {
        KernelType.XLA: "ref.moe_gmm_ref",
        KernelType.PALLAS: "moe_gmm (compiled)",
        KernelType.PALLAS_INTERPRET: "moe_gmm (interpret)",
    },
    "ssd": {
        KernelType.XLA: "ref.ssd_scan_ref",
        KernelType.PALLAS: "ssd_scan (compiled)",
        KernelType.PALLAS_INTERPRET: "ssd_scan (interpret)",
    },
    "ssd_step": {
        KernelType.XLA: "models.layers.ssd_decode_step",
        KernelType.PALLAS: "models.layers.ssd_decode_step (jnp; see above)",
        KernelType.PALLAS_INTERPRET: "models.layers.ssd_decode_step (jnp)",
    },
}


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              scale: Optional[float] = None,
              backend: Union[str, KernelType, None] = None) -> jnp.ndarray:
    b = normalize(backend)
    if b is KernelType.XLA:
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       scale=scale)
    return _fa_pallas(q, k, v, causal=causal, window=window, scale=scale,
                      interpret=(b is KernelType.PALLAS_INTERPRET))


def decode_attention(q, k, v, valid_len, *, scale: Optional[float] = None,
                     backend: Union[str, KernelType, None] = None
                     ) -> jnp.ndarray:
    b = normalize(backend)
    if b is KernelType.XLA:
        return ref.decode_attention_ref(q, k, v, valid_len, scale=scale)
    return _dec_pallas(q, k, v, valid_len, scale=scale,
                       interpret=(b is KernelType.PALLAS_INTERPRET))


def moe_gmm(x, w_gate, w_up, w_down, group_sizes, layer=None, *,
            backend: Union[str, KernelType, None] = None) -> jnp.ndarray:
    """SwiGLU expert FFN over rows sorted by expert (``moe_gmm.py``);
    ``layer`` indexes stacked weights."""
    b = normalize(backend)
    if b is KernelType.XLA:
        return ref.moe_gmm_ref(x, w_gate, w_up, w_down, group_sizes, layer)
    return _gmm_pallas(x, w_gate, w_up, w_down, group_sizes, layer,
                       interpret=(b is KernelType.PALLAS_INTERPRET))


def ssd(x, dt, A, Bm, Cm, *, chunk: int = 64,
        init_state: Optional[jnp.ndarray] = None,
        backend: Union[str, KernelType, None] = None
        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b = normalize(backend)
    if b is KernelType.XLA:
        return ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk,
                                init_state=init_state)
    S = x.shape[1]
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    y, st = _ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk, init_state=init_state,
                        interpret=(b is KernelType.PALLAS_INTERPRET))
    return y[:, :S], st


def ssd_step(state, x, dt, A, Bm, Cm, *,
             backend: Union[str, KernelType, None] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token SSM recurrence — every backend routes to the jnp
    reference (see KERNEL_TABLE); the entry point exists so call sites
    dispatch uniformly and the choice is recorded in one place."""
    normalize(backend)          # validate even though the impl is shared
    from ..models.layers import ssd_decode_step
    return ssd_decode_step(state, x, dt, A, Bm, Cm)
