"""Pallas TPU kernels for the data-plane hot spots.

The paper (Archipelago) is a control-plane contribution with no kernel of
its own; these kernels are the compute hot spots of the *workload it
schedules* (model serving): prefill flash attention, flash-decoding over KV
caches, the Mamba2 SSD scan, and the grouped expert matmul of a
mixture-of-experts layer.

Each kernel has: <name>.py (pl.pallas_call + explicit BlockSpec VMEM tiling),
an oracle in ref.py (pure jnp), and a dispatching wrapper in ops.py.
Validated in interpret mode on CPU; compiled path targets TPU v5e.
"""
from . import ops, ref
from .flash_attention import flash_attention
from .decode_attention import decode_attention
from .moe_gmm import moe_gmm
from .ssd_scan import ssd_scan

__all__ = ["ops", "ref", "flash_attention", "decode_attention", "moe_gmm",
           "ssd_scan"]
