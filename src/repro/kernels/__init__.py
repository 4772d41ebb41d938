"""Pallas TPU kernels for the compute hot-spots (DESIGN.md §3.3).

Each kernel ships as <name>/{kernel.py, ops.py, ref.py}: the pallas_call
with explicit BlockSpec VMEM tiling, the public wrapper, and the
pure-jnp oracle the tests assert against (interpret mode on CPU). The
OTA channel wrappers live in ``repro.kernels.ota_channel.ops``.
"""
from repro.kernels.masked_gradnorm.ops import (
    masked_gradnorm, masked_gradnorm_reference,
)
from repro.kernels.flash_attention.ops import (
    flash_attention, flash_attention_reference,
)

__all__ = [
    "masked_gradnorm", "masked_gradnorm_reference",
    "flash_attention", "flash_attention_reference",
]
