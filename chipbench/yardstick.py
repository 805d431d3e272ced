"""The benchmark's own constants and arithmetic, kept apart from the program.

Peaks of each chip, the operations and bytes a kernel's call must move, and
the percentile rule.  Nothing here imports the program, so a change to
``src/`` cannot move the yardstick.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Sequence, Tuple


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
# kernel implements it).  Bytes are those of the op's inputs and outputs,
# counted per operand, so that a reader can leave out an operand whose
# bytes never cross HBM.
# ---------------------------------------------------------------------------

BF16 = 2
F32 = 4

Parts = Dict[str, float]     # operand name -> bytes


def attention_parts(b: int, s: int, hq: int, hkv: int, hd: int) -> Parts:
    """q, k, v read and o written once, in bf16."""
    return {"q": BF16 * b * s * hq * hd, "k": BF16 * b * s * hkv * hd,
            "v": BF16 * b * s * hkv * hd, "o": BF16 * b * s * hq * hd}


def attention_cost(b: int, s: int, hq: int, hkv: int, hd: int,
                   causal: bool = True) -> Tuple[float, float]:
    """Self-attention over ``s`` positions: QK^T and PV are 2*s*s*hq*hd
    FLOPs each per row, halved by a causal mask.  Bytes: q, k, v read and
    o written once, in bf16."""
    flops = 4.0 * b * s * s * hq * hd * (0.5 if causal else 1.0)
    return flops, sum(attention_parts(b, s, hq, hkv, hd).values())


def decode_attention_parts(b: int, length: int, hq: int, hkv: int,
                           hd: int) -> Parts:
    """q read and o written for one token per row; ``length`` cached keys
    and values read, in bf16."""
    return {"q": BF16 * b * hq * hd, "k": BF16 * b * length * hkv * hd,
            "v": BF16 * b * length * hkv * hd, "o": BF16 * b * hq * hd}


def decode_attention_cost(b: int, length: int, hq: int, hkv: int,
                          hd: int) -> Tuple[float, float]:
    """One query token per row against ``length`` cached keys and values."""
    flops = 4.0 * b * length * hq * hd
    return flops, sum(decode_attention_parts(b, length, hq, hkv,
                                             hd).values())


def ssd_parts(b: int, s: int, h: int, p: int, n: int) -> Parts:
    """x, dt, B, C read and y written in bf16; the carried state (h, p, n)
    read and written in f32."""
    return {"x": BF16 * b * s * h * p, "dt": BF16 * b * s * h,
            "B": BF16 * b * s * n, "C": BF16 * b * s * n,
            "state": F32 * b * h * p * n, "y": BF16 * b * s * h * p,
            "state_out": F32 * b * h * p * n}


def ssd_cost(b: int, s: int, h: int, p: int, n: int) -> Tuple[float, float]:
    """Mamba2 SSD over ``s`` positions: per position and head, the state
    update (dt * B outer x) and the read-out C . state are 2*p*n FLOPs each,
    and the decay another p*n; 6*p*n bounds the three from above by the
    chunked form's intra-chunk term.  Bytes: ``ssd_parts``."""
    flops = 6.0 * b * s * h * p * n
    return flops, sum(ssd_parts(b, s, h, p, n).values())


def scaled(parts: Parts, k: float) -> Parts:
    """Each operand's bytes times ``k`` (the calls of a batch)."""
    return {r: k * v for r, v in parts.items()}


def summed(parts: Sequence[Parts]) -> Parts:
    """Each operand's bytes summed over several calls."""
    out: Parts = {}
    for ps in parts:
        for r, v in ps.items():
            out[r] = out.get(r, 0.0) + v
    return out


def bound_seconds(flops: float, nbytes: float, pk: Peaks) -> float:
    """The least time the chip could take: max(flops/peak, bytes/bw)."""
    return max(flops / pk.flops_bf16, nbytes / pk.hbm_bw)
