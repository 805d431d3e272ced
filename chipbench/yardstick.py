"""The benchmark's own constants and arithmetic, kept apart from the program.

Peaks of each chip, the operations and bytes a kernel's call must move, and
the percentile rule.  Nothing here imports the program, so a change to
``src/`` cannot move the yardstick.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Sequence, Tuple


@dataclass(frozen=True)
class Peaks:
    """Published peaks of one chip."""

    flops_bf16: float     # FLOP/s
    hbm_bw: float         # bytes/s
    hbm_bytes: float      # device memory
    source: str


# keyed by jax.Device.device_kind; a kind that is not here is an error
PEAKS = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s'),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile over all values (``inf`` counts as a value:
    a request that never completed lies above every finite latency)."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    k = max(1, math.ceil(p / 100.0 * len(s)))
    return s[k - 1]


# ---------------------------------------------------------------------------
# Work one kernel call must do, from the shapes of the op (not from how a
# kernel implements it).  Bytes are those of the op's inputs and outputs.
# ---------------------------------------------------------------------------

BF16 = 2
F32 = 4


def attention_cost(b: int, s: int, hq: int, hkv: int, hd: int,
                   causal: bool = True) -> Tuple[float, float]:
    """Self-attention over ``s`` positions: QK^T and PV are 2*s*s*hq*hd
    FLOPs each per row, halved by a causal mask.  Bytes: q, k, v read and
    o written once, in bf16."""
    flops = 4.0 * b * s * s * hq * hd * (0.5 if causal else 1.0)
    nbytes = BF16 * (b * s * hq * hd * 2 + b * s * hkv * hd * 2)
    return flops, nbytes


def decode_attention_cost(b: int, length: int, hq: int, hkv: int,
                          hd: int) -> Tuple[float, float]:
    """One query token per row against ``length`` cached keys and values."""
    flops = 4.0 * b * length * hq * hd
    nbytes = BF16 * (b * length * hkv * hd * 2 + b * hq * hd * 2)
    return flops, nbytes


def ssd_cost(b: int, s: int, h: int, p: int, n: int) -> Tuple[float, float]:
    """Mamba2 SSD over ``s`` positions: per position and head, the state
    update (dt * B outer x) and the read-out C . state are 2*p*n FLOPs each,
    and the decay another p*n; 6*p*n bounds the three from above by the
    chunked form's intra-chunk term.  Bytes: x, dt, B, C read and y written
    in bf16, the carried state (h, p, n) read and written in f32."""
    flops = 6.0 * b * s * h * p * n
    nbytes = (BF16 * b * s * (h * p * 2 + h + 2 * n)
              + F32 * b * h * p * n * 2)
    return flops, nbytes


def bound_seconds(flops: float, nbytes: float, pk: Peaks) -> float:
    """The least time the chip could take: max(flops/peak, bytes/bw)."""
    return max(flops / pk.flops_bf16, nbytes / pk.hbm_bw)
