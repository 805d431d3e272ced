"""Production mesh construction and the per-chip peak table.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count is locked at first jax init, and only
``dryrun.py`` is allowed to force 512 host devices).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 v5e chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever this host actually has (CPU smoke tests / examples)."""
    n = len(jax.devices())
    return jax.make_mesh((n // model, model), ("data", "model"))


@dataclass(frozen=True)
class PeakRates:
    """Published per-chip peaks of one device kind."""

    flops_bf16: float             # FLOP/s
    hbm_bw: float                 # bytes/s
    ici_bw: float                 # bytes/s per link
    source: str


# The one peak table, keyed by ``jax.Device.device_kind``.
V5E = "TPU v5 lite"
PEAKS = {
    V5E: PeakRates(
        flops_bf16=197e12, hbm_bw=819e9,
        ici_bw=50e9,              # 1,600 Gbit/s over four links
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '819 GB/s HBM, 1,600 Gbit/s ICI'),
}


def peak_rates(device_kind: str) -> PeakRates:
    """Peaks of ``device_kind``.  A kind that is not in the table is an
    error, never a default: a CPU host that wants a v5e bound asks for
    ``peak_rates(V5E)`` by name."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
