"""Pallas TPU kernel: per-task masked L2 gradient norms (FedGradNorm, eq. 6).

A tiled masked reduction: grid (task_blocks, col_blocks); the (T_blk, 1)
output block is revisited across the column grid dimension (innermost,
sequential on TPU), accumulating partial sums of (M∘g)² in fp32 and taking
the square root on the last visit. Column tiles are (T_blk, 1024) —
8 sublanes x 128 lanes x 8 — sized so a g-tile + mask-tile fit comfortably
in VMEM at any task-block height.

The mask row is broadcast across the task block from a (1, col_blk) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

COL_BLOCK = 1024
TASK_BLOCK = 8


def _gradnorm_kernel(g_ref, m_ref, out_ref, *, n_col_blocks):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)           # (1, colblk)
    part = jnp.sum((g * m) ** 2, axis=1, keepdims=True)
    out_ref[...] += part

    @pl.when(j == n_col_blocks - 1)
    def _finalize():
        out_ref[...] = jnp.sqrt(out_ref[...])


def masked_gradnorm_pallas(
    g: jax.Array,       # (T, P) — T multiple of TASK_BLOCK, P of COL_BLOCK
    mask: jax.Array,    # (1, P)
    *,
    task_block: int = TASK_BLOCK,
    col_block: int = COL_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    t, p = g.shape
    task_block = min(task_block, t)
    col_block = min(col_block, p)
    assert t % task_block == 0 and p % col_block == 0, (g.shape,)
    grid = (t // task_block, p // col_block)

    kernel = functools.partial(_gradnorm_kernel, n_col_blocks=grid[1])
    out = pl.pallas_call(
        kernel,
        grid=grid,
        name="masked_gradnorm",
        in_specs=[
            pl.BlockSpec((task_block, col_block), lambda i, j: (i, j)),
            pl.BlockSpec((1, col_block), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((task_block, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, 1), jnp.float32),
        interpret=interpret,
    )(g, mask)
    return out[:, 0]
