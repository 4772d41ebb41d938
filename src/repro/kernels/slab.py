"""Shared slab-layout helpers for the Pallas kernel wrappers.

Every kernel in this package views its operands as lane-aligned slabs:
the last dimension is the 128-wide VPU lane axis, the second-to-last is
padded to a multiple of 8 sublanes (f32 packing). Historically each
wrapper re-implemented the ravel/pad/reshape dance; this module is the
single home for that logic — used by masked_gradnorm, flash_attention
and the flat-pack layout (repro.common.flatpack).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANE = 128            # VPU lane width — last dim of every slab
SUBLANE = 8           # f32 sublane packing — row-count multiple
ROW_QUANTUM = LANE * SUBLANE   # smallest lane-aligned flat section (1024)


def on_tpu() -> bool:
    """Whether the default backend is TPU, resolved NOW — not at import.

    Kernel wrappers must call this at trace time (inside the jit'd
    function or when resolving a ``None`` default), never bake it into a
    module-level constant: backend selection via ``jax.config`` /
    ``JAX_PLATFORMS`` after import would otherwise silently pin TPU runs
    to interpret-mode kernels (the 28x-slow class of bug —
    BENCH_kernels.json's masked_gradnorm interpret row).
    """
    return jax.default_backend() == "tpu"


def round_up(n: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``n`` (0 stays 0)."""
    return -(-n // m) * m


def slab_rows(n: int) -> int:
    """Rows of the (rows, LANE) slab holding ``n`` flat elements (>= 8)."""
    return max(SUBLANE, round_up(-(-n // LANE), SUBLANE))


def pad_to_lanes(x: jax.Array):
    """Ravel ``x`` into a zero-padded (rows, LANE) slab.

    Returns (slab, n) where ``n`` is the original element count —
    ``slab.reshape(-1)[:n].reshape(x.shape)`` round-trips exactly.
    """
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = slab_rows(n)
    flat = jnp.pad(flat, (0, rows * LANE - n))
    return flat.reshape(rows, LANE), n


def pad_axis(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    """Zero-pad one axis of ``x`` up to a multiple of ``multiple``."""
    pad = -x.shape[axis] % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)
